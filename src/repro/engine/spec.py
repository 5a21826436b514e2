"""``DrawSpec`` — one frozen description of how a query executes.

Before the API consolidation (DESIGN.md §13) the engine's entry points
scattered the same knobs across keyword arguments: ``compile`` took
``rep/method/project``, ``sample`` added ``cap/acap/mesh/axes``,
``sample_batch`` repeated all of them, and the plan layers
(``CompiledPlan``, ``ShardedPlan``) re-declared the subset they bake into
executors. ``DrawSpec`` is the single value object for all of it:

  * **frozen + hashable** — a spec can key dictionaries, land in plan-cache
    keys, and be shared across threads;
  * **structure vs runtime** — ``rep``/``method``/``project``/``narrow``
    are *plan identity* (baked into jitted executors, part of the plan
    cache key via ``fingerprint.executor_key``); ``cap``/``acap`` are
    *runtime statics* (each distinct value is one cached trace inside a
    plan, never a new plan); ``mesh``/``axes`` select the sharded path
    (part of the *sharded* plan key via ``mesh_fingerprint``);
  * **None = inherit** — every field defaults to "use the engine/plan
    default", so ``DrawSpec()`` is exactly the legacy no-kwargs call.

Every engine entry point accepts ``spec=``; the legacy kwargs keep working
through one normalization shim (``QueryEngine._resolve_spec``), where an
explicitly passed kwarg overrides the corresponding spec field.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

__all__ = ["DrawSpec", "merge_spec"]

_REPS = (None, "csr", "usr", "both")
_METHODS = ("exprace", "ptbern_flat")
_KERNELS = ("auto", "fused", "paged", "pernode", "reference")


@dataclasses.dataclass(frozen=True)
class DrawSpec:
    """How a draw (or full join) executes. All fields optional; ``None``
    means "inherit the engine/plan default".

    rep      index representation (``csr``/``usr``/``both``); None lets the
             plan pick (engine default, upgraded to the fused kernel when
             available — an explicit rep always wins, DESIGN.md §4).
    method   position-sampling method for Poisson draws (``exprace`` or
             ``ptbern_flat``; default exprace).
    project  bag-projection attributes A for beta_y(pi_A(Q^)) queries.
    cap      sample capacity override (static shape; one cached trace per
             value inside a plan — never a new plan).
    acap     EXPRACE arrival-scratch capacity override.
    narrow   EXPRACE's integer pipeline in int32: None = auto (on iff the
             join certifies it, ``sampling.exprace_fits_int32``: n + R + 1
             and the arrival capacity below 2^31), True = force on
             (raises where the certificate fails), False = force off.
    kernels  draw-kernel route (DESIGN.md §14/§15): ``auto`` = the
             one-launch fused draw iff capable and the active
             ``KernelPolicy`` prefers it, degrading to the *paged* route
             (sample launch + page-streamed walk) when only the index's
             pages fit the VMEM budget, else the multi-launch per-node
             path; ``fused`` = require the fused kernel (raises at bind if
             unavailable); ``paged`` = require the paged route (raises if
             the index is not in the paged regime); ``reference`` = the
             fused pipeline as plain traced jnp (the bit-identity oracle);
             ``pernode`` = always the F64 multi-launch path (the precision
             arbiter).
    mesh     device mesh: route through the sharded plan (DESIGN.md §8).
    axes     mesh axes to partition the root over (None = shard planner).
    """

    rep: Optional[str] = None
    method: str = "exprace"
    project: Optional[Tuple[str, ...]] = None
    cap: Optional[int] = None
    acap: Optional[int] = None
    narrow: Optional[bool] = None
    kernels: str = "auto"
    mesh: Optional[object] = None
    axes: Optional[Tuple[str, ...]] = None

    def __post_init__(self):
        # Normalize sequence-typed fields so equal specs hash equal.
        if self.project is not None and not isinstance(self.project, tuple):
            object.__setattr__(self, "project", tuple(self.project))
        if self.axes is not None and not isinstance(self.axes, tuple):
            object.__setattr__(self, "axes", tuple(self.axes))
        if self.rep not in _REPS:
            raise ValueError(f"rep must be csr|usr|both|None, got {self.rep!r}")
        if self.method not in _METHODS:
            raise ValueError(
                f"method must be one of {_METHODS}, got {self.method!r}")
        if self.kernels not in _KERNELS:
            raise ValueError(
                f"kernels must be one of {_KERNELS}, got {self.kernels!r}")

    # -- derived views -------------------------------------------------------
    def plan_view(self, rep: str) -> "DrawSpec":
        """The spec a ``CompiledPlan`` stores: plan-identity fields only,
        with ``rep`` pinned to the concrete representation the index was
        built with. Runtime fields (cap/acap) and routing fields
        (mesh/axes) are stripped — they never define plan identity."""
        return DrawSpec(rep=rep, method=self.method, project=self.project,
                        narrow=self.narrow, kernels=self.kernels)

    def with_overrides(self, **kw) -> "DrawSpec":
        """``dataclasses.replace`` restricted to non-None overrides —
        the merge rule of the legacy-kwargs shim."""
        return merge_spec(self, **kw)


def merge_spec(spec: Optional[DrawSpec], **kw) -> DrawSpec:
    """The one normalization rule behind every entry point's legacy
    kwargs: start from ``spec`` (or an empty ``DrawSpec``) and overlay
    every kwarg that was explicitly passed (i.e. is not None)."""
    base = spec if spec is not None else DrawSpec()
    over = {k: v for k, v in kw.items() if v is not None}
    return dataclasses.replace(base, **over) if over else base
