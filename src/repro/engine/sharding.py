"""Sharded execution as a first-class engine path (DESIGN.md §8).

Two pieces:

  * ``plan_shards`` — the shard planner: picks the shard axes/count from
    the mesh shape, the root relation size, and the engine's
    ``CapacityPolicy`` (never shards over model-parallel axes; never splits
    the root below ``min_shard_rows`` rows per shard);
  * ``ShardedPlan`` — the sharded analogue of ``CompiledPlan``: a stacked
    per-shard index (built by ``core.distributed.build_stacked_shred``,
    held in the engine's shred cache) plus jitted shard_map executors for
    both entry points — per-shard Poisson trials with device-folded keys
    and a psum'd global count, and per-shard Yannakakis flatten whose
    gathered shards concatenate to exactly the single-device flatten.

Poisson sampling shards without coordination because trials are
independent per tuple; the device-folded key scheme
(``core.distributed.fold_shard_key``) makes the result distributionally
identical to a global draw and bit-reproducible against a host-side
emulation that folds the shard index into the same base key.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.core import estimate, probe, sampling
from repro.core.distributed import (StackedShred, fold_shard_key,
                                    place_stacked)
from repro.core.jointree import JoinQuery
from repro.core.poisson import JoinSample
from repro.core.relations import place_column

from . import executors
from .capacity import CapacityPolicy, DEFAULT_POLICY
from .plan import bit_columns, draw_call, redraw_with_doubling, resolve_narrow
from .spec import DrawSpec

__all__ = ["ShardPlan", "ShardedPlan", "plan_shards", "BATCH_AXES"]

I64 = jnp.int64

# Data-like mesh axes the root may be partitioned over; model-parallel axes
# replicate the index (they shard the *model*, not the data).
BATCH_AXES = ("pod", "data")


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """The planner's verdict: which mesh axes shard the root, into how many
    blocks. ``axes == ()`` means "do not shard" (route to the single-device
    plan)."""

    axes: Tuple[str, ...]
    num_shards: int


def plan_shards(
    mesh: Mesh, root_rows: int,
    policy: CapacityPolicy = DEFAULT_POLICY,
    axes: Optional[Tuple[str, ...]] = None,
) -> ShardPlan:
    """Pick shard axes and count from the mesh, root size, and policy.

    Auto mode (``axes=None``) uses the mesh's data-like axes (``pod``,
    ``data`` — or the sole axis of a single-axis mesh), then drops trailing
    axes while a shard would fall under ``policy.min_shard_rows`` root rows
    — finer splits are all padding and no work. An explicit ``axes`` tuple
    is honored as-is (the dry-run and facade callers own their layout).
    """
    if axes is None:
        picked = tuple(a for a in BATCH_AXES if a in mesh.axis_names)
        if not picked and len(mesh.axis_names) == 1 \
                and mesh.axis_names[0] != "model":
            picked = tuple(mesh.axis_names)  # single-axis custom meshes

        def count(ax):
            return int(np.prod([mesh.shape[a] for a in ax])) if ax else 1

        while picked and count(picked) > 1 \
                and root_rows // count(picked) < policy.min_shard_rows:
            picked = picked[:-1]
        if count(picked) <= 1:
            return ShardPlan((), 1)
        return ShardPlan(picked, count(picked))
    axes = tuple(axes)
    n = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    return ShardPlan(axes, n)


def auto_mesh(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis in Auto mode. ``jax.make_mesh`` builds
    Explicit axes by default, and a shard_map output over an Explicit mesh
    carries its sharding in its type — indexing it on the host raises. The
    engine runs its executors over the Auto twin, so results index like
    any array whichever way the caller built the mesh."""
    auto = (AxisType.Auto,) * len(mesh.axis_names)
    if tuple(mesh.axis_types) == auto:
        return mesh
    return Mesh(mesh.devices, mesh.axis_names, axis_types=auto)


class ShardedPlan:
    """One sharded entry of the plan cache: stacked index + shard_map
    executors, keyed by (query fingerprint, rep, method, project, mesh
    shape, axes) — the sharded analogue of ``CompiledPlan``.

    Everything data-dependent (the PRNG key, capacity overrides) stays a
    runtime argument; each distinct (cap, acap) pair is one cached
    shard_map trace, so warm sharded calls are a dict lookup plus one
    cached dispatch — zero shred rebuilds (asserted by ``CacheStats`` in
    ``tests/test_sharded_engine.py``).
    """

    def __init__(self, query: JoinQuery, spec: DrawSpec,
                 mesh: Mesh, axes: Tuple[str, ...],
                 stacked: StackedShred,
                 policy: CapacityPolicy = DEFAULT_POLICY):
        if spec.method != "exprace":
            # ptbern_flat needs a static per-shard flat count; shard join
            # sizes differ, so only the arrival-race sampler shards.
            raise ValueError(f"sharded sampling supports method='exprace', "
                             f"got {spec.method!r}")
        self.query = query
        self.spec = spec  # resolved plan-identity spec (DrawSpec.plan_view)
        self._base_rep = "usr" if spec.rep == "both" else spec.rep
        self.method = spec.method
        self.project = spec.project
        self.mesh = auto_mesh(mesh)
        self.axes = tuple(axes)
        self.sharding = NamedSharding(self.mesh, P(self.axes))
        self.policy = policy
        self._samplers: Dict[Tuple[int, int, bool], callable] = {}
        self._batched_samplers: Dict[Tuple[int, int, bool], callable] = {}
        self._flattener = None
        self._bind_stacked(stacked)

    def _bind_stacked(self, stacked: StackedShred) -> None:
        # Shard s of every stacked leaf lives on the mesh's device s (a
        # no-op when the engine already placed the index this way): an
        # index left on one device would be re-sharded by every call.
        stacked = place_stacked(stacked, self.sharding)
        self.stacked = stacked
        # Executor rep (probe.select_rep — the same policy as the
        # single-device plan, over the stacked arena with its leading
        # shard dim; DESIGN.md §4). Baked into the shard_map partials, so
        # a rebind that flips it invalidates the executor caches (a
        # retrace, not a rebuild — same economics as a capacity change).
        rep = probe.select_rep(stacked.shred, self._base_rep)
        if getattr(self, "rep", None) != rep:
            self._samplers.clear()
            self._batched_samplers.clear()
            self._flattener = None
        self.rep = rep
        self.num_shards = stacked.num_shards
        self.join_sizes = stacked.join_sizes
        self._bit_columns = bit_columns(stacked.shred, self.project)
        # Global flat offset of each shard's position space: shard flattens
        # concatenate to the global flatten, so shard-local position + base
        # is the same coordinate the single-device plan reports.
        self._bases = np.concatenate(
            [[0], np.cumsum(self.join_sizes)])[:-1].astype(np.int64)

        if stacked.p is not None:
            # Bind-time tables in host numpy over the (S, R) stack, as in
            # the single-device plan: the TPU compiler takes minutes over
            # the f64 scans in them.
            w, p = np.asarray(stacked.w), np.asarray(stacked.p)
            means = estimate.expected_sample_size(w, p)
            stds = estimate.sample_std(w, p)
            masses = estimate.exprace_arrival_mass(w, p)
            # One static capacity for every shard: plan for the heaviest.
            # Sticky across rebinds (DESIGN.md §11): a delta that lowers the
            # estimate keeps the already-traced capacity; growth retraces.
            self.cap = max(getattr(self, "cap", None) or 0, self.policy.plan(
                float(means.max(initial=0.0)), float(stds.max(initial=1.0))))
            mass = float(masses.max(initial=0.0))
            self.acap = max(getattr(self, "acap", 0),
                            self.policy.plan(mass * 1.1 + 8, mass ** 0.5))
            self.massE = jax.device_put(sampling.exprace_mass(w, p),
                                        self.sharding)
        else:
            self.cap = None
            self.acap = 0
            self.massE = None
        # EXPRACE's width, as in the single-device plan: the global join
        # size bounds every shard's. Samplers are cached per width.
        self._narrow = resolve_narrow(self.spec.narrow, stacked.join_size,
                                      int(stacked.w.shape[-1]), self.acap)
        flat_cap = max(getattr(self, "flat_cap", 0),
                       self.policy.flatten_capacity(
                           max(self.join_sizes, default=0)))
        if getattr(self, "flat_cap", None) != flat_cap:
            self._flattener = None  # static cap changed: next flatten retraces
        self.flat_cap = flat_cap

    def rebind_stacked(self, stacked: StackedShred) -> "ShardedPlan":
        """Swap in an (incrementally resharded) stacked index for a newer
        snapshot, keeping the shard_map executor caches. A delta that
        preserves per-shard shapes and planned capacities costs zero
        retraces on the next warm draw (DESIGN.md §11)."""
        self._bind_stacked(stacked)
        return self

    # -- derived -------------------------------------------------------------
    @property
    def join_size(self) -> int:
        return self.stacked.join_size

    def expected_k(self) -> float:
        if self.stacked.p is None:
            raise ValueError("plan has no prob_var")
        return float(estimate.expected_sample_size(
            self.stacked.w.reshape(-1), self.stacked.p.reshape(-1)))

    # -- shard_map executors -------------------------------------------------
    @staticmethod
    def _local_sample(shred, w, p, prefE, massE, key, *, cap, acap, rep,
                      method, project, axes, narrow=False):
        key = fold_shard_key(key, axes)
        # Drop the leading (stacked) singleton shard dim.
        shred, w, p, prefE, massE = jax.tree.map(
            lambda x: x[0], (shred, w, p, prefE, massE))
        s = executors._sample_jit(shred, w, p, prefE, key, cap=cap, rep=rep,
                                  method=method, acap=acap, project=project,
                                  narrow=narrow, massE=massE)
        total = jax.lax.psum(s.count, axes)
        # Re-add the shard dim so out_specs can concatenate across shards.
        return jax.tree.map(lambda x: x[None], s), total

    @staticmethod
    def _local_sample_batch(shred, w, p, prefE, massE, keys, *, cap, acap,
                            rep, method, project, axes, narrow=False):
        """The batched shard body (DESIGN.md §10): shard_map outside, vmap
        inside. Each lane folds the same shard coordinate into its own base
        key, so lane ``b`` reproduces the single-draw sharded path under
        ``keys[b]`` bit-for-bit; one psum reports the (B,) global counts."""
        shred, w, p, prefE, massE = jax.tree.map(
            lambda x: x[0], (shred, w, p, prefE, massE))

        def one(k):
            return executors._sample_jit(
                shred, w, p, prefE, fold_shard_key(k, axes), cap=cap,
                rep=rep, method=method, acap=acap, project=project,
                narrow=narrow, massE=massE)

        s = jax.vmap(one)(keys)              # leaves: (B, ...)
        totals = jax.lax.psum(s.count, axes)  # (B,) global counts
        return jax.tree.map(lambda x: x[None], s), totals

    @staticmethod
    def _local_flatten(shred, prefE, *, cap, rep):
        shred, prefE = jax.tree.map(lambda x: x[0], (shred, prefE))
        n = prefE[-1]  # this shard's true join size (pads are weight-0)
        pos = jnp.minimum(jnp.arange(cap, dtype=I64), jnp.maximum(n - 1, 0))
        cols = probe.get(shred, pos, rep=rep)
        return jax.tree.map(lambda x: x[None], cols), n[None]

    def _narrow_call(self, cap: int, acap: int) -> bool:
        """Whether a call at these capacities runs EXPRACE in int32: the
        bound verdict, at capacities the certificate still covers."""
        return self._narrow and sampling.exprace_fits_int32(
            self.join_size, int(self.stacked.w.shape[-1]), acap, cap)

    def _sampler(self, cap: int, acap: int, narrow: bool):
        fn = self._samplers.get((cap, acap, narrow))
        if fn is None:
            spec = P(self.axes)
            fn = jax.jit(jax.shard_map(
                partial(self._local_sample, cap=cap, acap=acap, rep=self.rep,
                        method=self.method, project=self.project,
                        axes=self.axes, narrow=narrow),
                mesh=self.mesh,
                in_specs=(spec, spec, spec, spec, spec, P()),
                out_specs=(spec, P()),
                check_vma=False,
            ))
            self._samplers[(cap, acap, narrow)] = fn
        return fn

    def _batched_sampler(self, cap: int, acap: int, narrow: bool):
        fn = self._batched_samplers.get((cap, acap, narrow))
        if fn is None:
            spec = P(self.axes)
            fn = jax.jit(jax.shard_map(
                partial(self._local_sample_batch, cap=cap, acap=acap,
                        rep=self.rep, method=self.method,
                        project=self.project, axes=self.axes,
                        narrow=narrow),
                mesh=self.mesh,
                in_specs=(spec, spec, spec, spec, spec, P()),
                out_specs=(spec, P()),
                check_vma=False,
            ))
            self._batched_samplers[(cap, acap, narrow)] = fn
        return fn

    def _flatten_fn(self):
        if self._flattener is None:
            spec = P(self.axes)
            self._flattener = jax.jit(jax.shard_map(
                partial(self._local_flatten, cap=self.flat_cap, rep=self.rep),
                mesh=self.mesh,
                in_specs=(spec, spec),
                out_specs=(spec, spec),
                check_vma=False,
            ))
        return self._flattener

    # -- execution -----------------------------------------------------------
    def sample_step(self, key, cap: Optional[int] = None,
                    acap: Optional[int] = None):
        """One independent global Poisson sample, left on device: the
        sharded JoinSample (leading dim = shards, shard-local positions)
        and the psum'd global count."""
        if self.stacked.p is None:
            raise ValueError("plan has no prob_var; use full_join")
        st = self.stacked
        cap, acap = cap or self.cap, acap or self.acap
        narrow = self._narrow_call(cap, acap)
        with draw_call("sharded", cap, acap, 1, self.num_shards, narrow,
                       self._bit_columns):
            return self._sampler(cap, acap, narrow)(
                st.shred, st.w, st.p, st.prefE, self.massE, key)

    def _call_overrides(self, spec: Optional[DrawSpec], cap, acap):
        """Per-call ``DrawSpec`` under the explicit kwargs (kwargs win).
        Only the runtime fields apply — rep/narrow are baked into the
        shard_map executors at bind time."""
        if spec is not None:
            cap = cap or spec.cap
            acap = acap or spec.acap
        return cap, acap

    def sample(self, key, cap: Optional[int] = None,
               acap: Optional[int] = None,
               spec: Optional[DrawSpec] = None) -> JoinSample:
        """One independent Poisson sample, gathered to a flat JoinSample.

        Positions are rebased to *global* flat coordinates (shard base +
        local), so the result is drop-in comparable with the single-device
        plan's samples; ``count`` reflects the gathered tuples (on overflow
        the draw is invalid and flagged, exactly like the unsharded path).
        """
        cap, acap = self._call_overrides(spec, cap, acap)
        if self.stacked.p is None:
            raise ValueError("plan has no prob_var; use full_join")
        if self.join_size == 0:
            return executors.empty_sample(self.stacked.shred,
                                          cap or self.cap)
        smp, _total = self.sample_step(key, cap=cap, acap=acap)
        return self._gather(
            {v: np.asarray(a) for v, a in smp.columns.items()},
            np.asarray(smp.positions), np.asarray(smp.count),
            bool(np.asarray(smp.overflow).any()))

    def _gather(self, columns, positions, counts, overflow) -> JoinSample:
        """Compact one draw's per-shard (S, cap) buffers into a flat
        JoinSample, rebasing positions to global flat coordinates (shard
        base + local). Shared by the single-draw and batched paths, so
        their per-draw results are bit-identical."""
        lane_cap = positions.shape[1]
        counts = np.minimum(counts, lane_cap)
        rows = np.repeat(np.arange(self.num_shards), counts)
        lanes = np.concatenate(
            [np.arange(c) for c in counts]) if rows.size else \
            np.zeros((0,), np.int64)
        out_cap = lane_cap * self.num_shards
        cols = {}
        for v, a in columns.items():
            buf = np.zeros((out_cap,), a.dtype)
            buf[:rows.size] = a[rows, lanes]
            cols[v] = place_column(buf)
        posbuf = np.zeros((out_cap,), np.int64)
        posbuf[:rows.size] = positions[rows, lanes] + self._bases[rows]
        return JoinSample(
            cols, jnp.asarray(posbuf),
            jnp.asarray(np.int64(rows.size)),
            jnp.asarray(bool(overflow)),
        )

    def sample_batch(self, keys, cap: Optional[int] = None,
                     acap: Optional[int] = None,
                     spec: Optional[DrawSpec] = None) -> JoinSample:
        """``B`` independent global Poisson draws in one shard_map dispatch
        (DESIGN.md §10): vmap over split keys inside each shard, one psum
        for the global counts. The gathered result carries a leading batch
        axis and lane ``b`` is bit-identical to ``self.sample(keys[b])``
        (same per-shard draws, same gather). Keys are bucketed to powers of
        two exactly like the single-device batched path.
        """
        cap, acap = self._call_overrides(spec, cap, acap)
        if self.stacked.p is None:
            raise ValueError("plan has no prob_var; use full_join")
        batch = int(keys.shape[0])
        if self.join_size == 0:
            return executors.empty_sample_batch(self.stacked.shred,
                                                cap or self.cap, batch)
        kpad, _ = executors.pad_batch_keys(keys)
        st = self.stacked
        cap, acap = cap or self.cap, acap or self.acap
        narrow = self._narrow_call(cap, acap)
        with draw_call("sharded", cap, acap, batch,
                       int(kpad.shape[0]) * self.num_shards, narrow,
                       self._bit_columns):
            smp, _totals = self._batched_sampler(cap, acap, narrow)(
                st.shred, st.w, st.p, st.prefE, self.massE, kpad)
        # Host gather per lane (padding lanes never gathered), then stack.
        columns = {v: np.asarray(a) for v, a in smp.columns.items()}
        positions = np.asarray(smp.positions)   # (S, Bp, cap)
        counts = np.asarray(smp.count)          # (S, Bp)
        overflow = np.asarray(smp.overflow)     # (S, Bp)
        lanes = [self._gather({v: a[:, b] for v, a in columns.items()},
                              positions[:, b], counts[:, b],
                              overflow[:, b].any())
                 for b in range(batch)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *lanes)

    def sample_auto(self, key, max_doublings: Optional[int] = None,
                    cap: Optional[int] = None,
                    acap: Optional[int] = None,
                    spec: Optional[DrawSpec] = None) -> JoinSample:
        """Redraw with doubled per-shard capacity until no shard overflows."""
        cap, acap = self._call_overrides(spec, cap, acap)
        return redraw_with_doubling(
            lambda c, a: self.sample(key, cap=c, acap=a),
            cap or self.cap, acap or self.acap,
            max_doublings if max_doublings is not None
            else self.policy.max_doublings)

    def full_join(self) -> Dict[str, jnp.ndarray]:
        """Yannakakis via the stacked index: per-shard flatten, gathered.

        Shard s's flatten is the global flatten restricted to root block s,
        so concatenating the valid prefixes reproduces the single-device
        ``flatten`` bit-for-bit, order included.
        """
        if self.join_size == 0:
            return {v: node.data.column(v)[0, :0]
                    for node in self.stacked.shred.root.nodes()
                    for v in node.owned}
        st = self.stacked
        cols, _ns = self._flatten_fn()(st.shred, st.prefE)
        out = {}
        for v, arr in cols.items():
            a = np.asarray(arr)
            out[v] = place_column(np.concatenate(
                [a[s, :self.join_sizes[s]] for s in range(self.num_shards)]))
        return out

    # -- dry-run support -----------------------------------------------------
    def lower_step(self):
        st = self.stacked
        key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype)
        args = jax.eval_shape(
            lambda: (st.shred, st.w, st.p, st.prefE, self.massE))
        return self._sampler(self.cap, self.acap).lower(*args, key)
