"""Compiled query plans: shred index + jitted executors + capacity metadata.

A ``CompiledPlan`` is the engine's unit of caching (DESIGN.md §7): the GYO
join tree has been run, the shred index built, and the sample executor
jitted. Everything data-dependent (the PRNG key, per-call capacity
overrides) stays a runtime argument, so one plan serves an unbounded stream
of independent sample draws and full-join flattens without rebuilding or
retracing.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import estimate, probe, sampling
from repro.core.jointree import JoinQuery
from repro.core.poisson import JoinSample
from repro.core.shred import Shred
from repro.core.yannakakis import flatten

from . import executors
from .capacity import CapacityPolicy, DEFAULT_POLICY
from .spec import DrawSpec

__all__ = ["CompiledPlan", "redraw_with_doubling"]


def redraw_with_doubling(draw, cap: int, acap: int, max_doublings: int):
    """The shared auto-capacity loop (host-side; DESIGN.md §7): call
    ``draw(cap, acap)`` until the returned sample reports no overflow,
    doubling both capacities between attempts. Used by the single-device
    ``CompiledPlan`` and the sharded ``ShardedPlan`` alike — overflow is
    always flagged, never silent."""
    for _ in range(max_doublings):
        s = draw(cap, acap)
        if not bool(s.overflow):
            return s
        cap *= 2
        acap *= 2
    raise RuntimeError("sample capacity still overflowing after doublings")


@dataclasses.dataclass
class CompiledPlan:
    """One (query fingerprint, spec identity) entry of the plan cache.

    ``spec`` is the *resolved* plan-identity ``DrawSpec``: concrete ``rep``
    (the representation the shred was built with), ``method``, ``project``
    and the ``narrow`` override — runtime fields (cap/acap) and routing
    fields (mesh/axes) are stripped by ``DrawSpec.plan_view`` before a plan
    is constructed. ``rep``/``method``/``project`` remain readable as
    properties for legacy callers.

    w / p / prefE are the root-level weight, probability, and exclusive
    prefix vectors (p is None for queries without ``prob_var`` — such plans
    serve full joins and uniform sampling only).
    """

    query: JoinQuery
    spec: DrawSpec
    shred: Shred
    policy: CapacityPolicy = DEFAULT_POLICY
    # ``rep_default`` (the concrete rep used when a call passes None) and
    # ``_narrow`` are derived per bound shred in ``_bind_shred`` — see
    # probe.select_rep (DESIGN.md §4).

    @property
    def rep(self) -> str:
        return self.spec.rep

    @property
    def method(self) -> str:
        return self.spec.method

    @property
    def project(self) -> Optional[Tuple[str, ...]]:
        return self.spec.project

    def __post_init__(self):
        self._default_cap = None
        self._arrival_cap = None
        self._bind_shred(self.shred)
        self._jit = executors.sample_executor(self.method, self.project)
        self._batched_jit = executors.batched_sample_executor(
            self.method, self.project)

    def _resolve_narrow(self, shred: Shred, auto_narrow: bool) -> bool:
        """Apply the spec's narrowing override to the auto verdict.
        Forcing ``narrow=True`` needs a packed (int32-safe) index — the
        arena's existence is the exactness proof (DESIGN.md §4)."""
        if self.spec.narrow is None:
            return auto_narrow
        if self.spec.narrow and shred.packed is None and shred.paged is None:
            raise ValueError(
                "DrawSpec(narrow=True) requires a packed int32 index "
                "(join < 2^31, no empty node); this shred has none")
        return self.spec.narrow

    def _bind_shred(self, shred: Shred) -> None:
        root = shred.root
        self.shred = shred
        self.w = root.weight
        self.prefE = shred.root_prefE
        # Host-cached once per bind: join_size is read on every draw's
        # capacity path, and int(device_scalar) is a blocking sync — per
        # dispatch it would stall the async prefetch ring (DESIGN.md §13).
        self._join_size = int(shred.join_size)
        # Executor rep + int32-narrowing selection (probe.select_rep,
        # DESIGN.md §4). Recomputed on every (re)bind: an upgraded index
        # may gain or lose its arena (int32 narrowing is per-snapshot).
        # Explicit per-call rep overrides still win in sample()/full_join().
        self.rep_default, auto_narrow = probe.select_rep(
            shred, "usr" if self.rep == "both" else self.rep)
        self._narrow = self._resolve_narrow(shred, auto_narrow)
        if self.query.prob_var is not None:
            if self.query.prob_var not in root.variables:
                raise AssertionError("build_plan must reroot prob_var to the root")
            self.p = root.data.column(self.query.prob_var)
            # Bind-time tables (capacities, the EXPRACE mass prefix, the
            # fused-draw operands) are float64 scans and reductions over
            # the plan-bound vectors: computed once per bind in host numpy,
            # which the TPU compiler would otherwise spend minutes on.
            w, p = np.asarray(self.w), np.asarray(self.p)
            # Sticky capacities (DESIGN.md §11): recomputed from the new
            # (w, p) but never shrunk below a capacity already traced —
            # a delta that lowers E[k] keeps the cached trace instead of
            # recompiling for a marginally smaller buffer. Growth retraces
            # once, which is the price of not overflowing.
            self._default_cap = max(self._default_cap or 0,
                                    self.policy.sample_capacity(w, p))
            self._arrival_cap = max(self._arrival_cap or 0,
                                    self.policy.arrival_capacity(w, p))
            place = partial(jax.device_put, device=self.w.sharding)
            self._massE = (place(sampling.exprace_mass(w, p))
                           if self.method == "exprace" else None)
            # Draw-kernel route (probe.select_draw, DESIGN.md §14), decided
            # once per bind like rep/narrow: the one-launch fused draw needs
            # its plan-bound operand vectors and a capable shred; recomputed
            # on rebind because a delta can gain or lose the packed arena.
            dparams = sampling.fused_draw_params(w, p,
                                                 np.asarray(self.prefE))
            self._route = probe.select_draw(
                shred, dparams, method=self.method,
                n=self._join_size if self.method == "ptbern_flat" else 0,
                kernels=self.spec.kernels)
            self._dparams = (jax.tree.map(place, dparams)
                             if self._route != "pernode" else None)
        else:
            self.p = None
            self._route = "pernode"
            self._dparams = None
            self._massE = None

    def rebind_shred(self, shred: Shred) -> "CompiledPlan":
        """Swap in an (incrementally upgraded) index for a newer snapshot,
        keeping the jitted executors — and with them every cached trace.
        A delta that preserves array shapes therefore costs zero retraces
        on the next warm draw (DESIGN.md §11)."""
        self._bind_shred(shred)
        return self

    @property
    def route(self) -> str:
        """The draw route bound for this snapshot (``probe.select_draw``)."""
        return self._route

    @property
    def narrow(self) -> bool:
        """Whether the sampler's integer searches run int32-narrowed."""
        return self._narrow

    # -- capacity planning ---------------------------------------------------
    @property
    def join_size(self) -> int:
        return self._join_size

    def expected_k(self) -> float:
        return float(estimate.expected_sample_size(self.w, self.p))

    def default_capacity(self) -> int:
        return (self._default_cap if self._default_cap is not None
                else self.policy.sample_capacity(self.w, self.p))

    def arrival_capacity(self) -> int:
        return (self._arrival_cap if self._arrival_cap is not None
                else self.policy.arrival_capacity(self.w, self.p))

    # -- execution -----------------------------------------------------------
    def _call_overrides(self, spec: Optional[DrawSpec], cap, rep, acap):
        """Merge a per-call ``DrawSpec`` under the explicit kwargs (kwargs
        win — the same precedence as the engine's normalization shim)."""
        if spec is not None:
            cap = cap or spec.cap
            acap = acap or spec.acap
            rep = rep or (spec.rep if spec.rep != "both" else None)
        return cap, rep, acap

    def sample(self, key, cap: Optional[int] = None, rep: Optional[str] = None,
               acap: Optional[int] = None,
               spec: Optional[DrawSpec] = None) -> JoinSample:
        """One independent Poisson sample draw (fresh randomness per key)."""
        cap, rep, acap = self._call_overrides(spec, cap, rep, acap)
        if self.p is None:
            raise ValueError("plan has no prob_var; use uniform_sample/full_join")
        cap = cap or self.default_capacity()
        if self.join_size == 0:
            return executors.empty_sample(self.shred, cap)
        acap = acap or (self.arrival_capacity() if self.method == "exprace" else 0)
        n = self.join_size if self.method == "ptbern_flat" else 0
        # An explicit per-call rep pins the multi-launch per-node path: the
        # fused route has no rep (its kernel walks the packed arena) and
        # draws from its own stream, so honoring the rep request means
        # honoring the per-node sampler with it.
        route = "pernode" if rep else self._route
        return self._jit(self.shred, self.w, self.p, self.prefE, key, cap=cap,
                         rep=rep or self.rep_default, n=n, acap=acap,
                         narrow=self._narrow, route=route,
                         dparams=self._dparams if route != "pernode" else None,
                         massE=self._massE)

    def sample_batch(self, keys, cap: Optional[int] = None,
                     rep: Optional[str] = None,
                     acap: Optional[int] = None,
                     spec: Optional[DrawSpec] = None) -> JoinSample:
        """``B`` independent Poisson draws in one dispatch (DESIGN.md §10).

        ``keys`` is a ``(B,)`` PRNG key vector (e.g. ``jax.random.split``);
        the result is a ``JoinSample`` whose leaves carry a leading batch
        axis — columns/positions ``(B, cap)``, count/overflow ``(B,)`` —
        and lane ``b`` is bit-identical to ``self.sample(keys[b])``. The
        key vector is padded to its power-of-two bucket before the
        dispatch, so warm batches of any size within a bucket never
        retrace; padding lanes are sliced off the result.
        """
        cap, rep, acap = self._call_overrides(spec, cap, rep, acap)
        if self.p is None:
            raise ValueError("plan has no prob_var; use uniform_sample/full_join")
        batch = int(keys.shape[0])
        cap = cap or self.default_capacity()
        if self.join_size == 0:
            return executors.empty_sample_batch(self.shred, cap, batch)
        acap = acap or (self.arrival_capacity() if self.method == "exprace" else 0)
        n = self.join_size if self.method == "ptbern_flat" else 0
        kpad, _ = executors.pad_batch_keys(keys)
        route = "pernode" if rep else self._route  # explicit rep pins pernode
        smp = self._batched_jit(self.shred, self.w, self.p, self.prefE, kpad,
                                cap=cap, rep=rep or self.rep_default, n=n,
                                acap=acap, narrow=self._narrow, route=route,
                                dparams=(self._dparams
                                         if route != "pernode" else None),
                                massE=self._massE)
        if int(kpad.shape[0]) != batch:
            smp = jax.tree.map(lambda x: x[:batch], smp)
        return smp

    def sample_auto(self, key, max_doublings: Optional[int] = None,
                    cap: Optional[int] = None,
                    acap: Optional[int] = None,
                    spec: Optional[DrawSpec] = None) -> JoinSample:
        """Redraw with doubled capacity until no overflow (host loop).
        ``cap``/``acap`` override the policy-derived starting capacities."""
        cap, _, acap = self._call_overrides(spec, cap, None, acap)
        if max_doublings is None:
            max_doublings = self.policy.max_doublings
        cap = cap or self.default_capacity()
        acap = acap or (self.arrival_capacity() if self.method == "exprace"
                        else 0)
        return redraw_with_doubling(
            lambda c, a: self.sample(key, cap=c, acap=a),
            cap, acap, max_doublings)

    def uniform_sample(self, key, p: float, cap: Optional[int] = None,
                       method: str = "hybrid") -> JoinSample:
        """beta_p with a fixed uniform probability (paper §6.1)."""
        n = self.join_size
        if cap is None:
            cap = self.policy.uniform_capacity(n, p)
        ps = executors.uniform_positions_fn(method)(key, p, n, cap)
        pos = jnp.minimum(ps.positions, max(n - 1, 0))
        cols = probe.get(self.shred, pos, rep=self.rep_default)
        return JoinSample(cols, ps.positions, ps.count, ps.overflow)

    def full_join(self, rep: Optional[str] = None,
                  spec: Optional[DrawSpec] = None) -> Dict[str, jnp.ndarray]:
        """Yannakakis via the cached index: flatten mu* by bulk probe."""
        _, rep, _ = self._call_overrides(spec, None, rep, None)
        return flatten(self.shred, rep=rep or self.rep_default)
