"""The unified query engine: plan once, index once, serve everything.

``QueryEngine`` is the paper's closing claim turned into an API surface
(DESIGN.md §7): one random-access shred index is a *uniform basis* for both
classical acyclic join processing (Yannakakis / SYA) and Poisson sampling
"without regret". The engine owns

  * a bound, immutable ``Database``;
  * a shred cache  — (query fingerprint, rep) -> built index;
  * a plan cache   — (query fingerprint, rep, method, project) -> jitted
    executors (``CompiledPlan``);
  * an explicit ``CapacityPolicy`` for static-shape buffer planning.

Repeated and batched queries with the same fingerprint skip GYO, index
construction, and XLA retracing entirely — the warm path is a dict lookup
plus one cached-trace dispatch. Both caches are LRU-bounded.

Sharded execution (DESIGN.md §8) is the same contract over a device mesh:
``sample(..., mesh=...)`` / ``full_join(..., mesh=...)`` route through a
shard planner to stacked per-shard indexes held in the *same* shred cache
(keyed by fingerprint x rep x mesh shape x shard count), so the warm
sharded path also performs zero index rebuilds.

The bound database is a *versioned snapshot* (DESIGN.md §11): cache keys
carry the snapshot version, and ``apply_delta`` advances the binding while
*upgrading* warm entries in place via incremental reshred — a small update
costs milliseconds of merge work, zero rebuilds, and (shapes permitting)
zero retraces, where ``rebind`` would throw everything away.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Dict, Optional, Tuple, Union

import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import config
from repro.core.database import Database
from repro.core.distributed import (
    StackedShred, build_stacked, place_stacked, reshard_incremental,
)
from repro.core.jointree import JoinQuery
from repro.core.poisson import JoinSample
from repro.core.shred import Shred, build_plan, build_shred, reshred_incremental
from repro.core import yannakakis

from .capacity import CapacityPolicy, DEFAULT_POLICY
from .fingerprint import (
    executor_key, mesh_fingerprint, plan_key, query_fingerprint,
    sharded_executor_key, sharded_plan_key,
)
from .plan import CompiledPlan
from .sharding import ShardPlan, ShardedPlan, auto_mesh, plan_shards
from .spec import DrawSpec, merge_spec

__all__ = ["QueryEngine", "CacheStats"]


@dataclasses.dataclass
class CacheStats:
    """Observable cache behavior (asserted in tests, reported by serve).

    Stacked (sharded) index builds and hits count in the same
    ``shred_builds`` / ``shred_hits`` — one index economy, two layouts.
    ``apply_delta`` reports its work separately: ``shred_upgrades`` /
    ``plan_upgrades`` count warm entries advanced incrementally (never
    through ``shred_builds`` — upgrading is precisely *not* rebuilding),
    and ``shards_reused`` / ``shards_rebuilt`` split the stacked-index
    treatment per shard (DESIGN.md §11).

    Stats are additive across engines: a replicated fleet (DESIGN.md §12)
    reports ``CacheStats.aggregate(r.engine.stats for r in replicas)`` —
    fingerprint-affine routing shows up there as exactly one ``plan_miss``
    per query shape per replica that ever saw it."""

    shred_builds: int = 0
    shred_hits: int = 0
    plan_hits: int = 0
    plan_misses: int = 0
    shred_upgrades: int = 0
    plan_upgrades: int = 0
    shards_reused: int = 0
    shards_rebuilt: int = 0

    def snapshot(self) -> "CacheStats":
        return dataclasses.replace(self)

    def __add__(self, other: "CacheStats") -> "CacheStats":
        if not isinstance(other, CacheStats):
            return NotImplemented
        return CacheStats(**{
            f.name: getattr(self, f.name) + getattr(other, f.name)
            for f in dataclasses.fields(CacheStats)})

    @classmethod
    def aggregate(cls, stats) -> "CacheStats":
        """Fleet-wide totals: the field-wise sum over an iterable of
        per-engine stats (empty iterable -> all-zero stats)."""
        total = cls()
        for s in stats:
            total = total + s
        return total


@dataclasses.dataclass
class _IndexEntry:
    """One shred-cache slot: the index plus what ``apply_delta`` needs to
    upgrade it — the query (for the join tree) and, for stacked indexes,
    the filtered base snapshot and shard count (DESIGN.md §11)."""

    index: Union[Shred, StackedShred]
    query: JoinQuery
    version: int
    base: Optional[Database] = None   # stacked entries: filtered base db
    num_shards: int = 0               # stacked entries only


class QueryEngine:
    """Plans, caches, and dispatches acyclic-join queries over one database.

    Usage::

        engine = QueryEngine(db)
        full   = engine.full_join(query)             # Yannakakis via index
        smp    = engine.poisson_sample(query, key)   # EXPRACE via same index

    Both entry points share the shred cache: the first call on a query
    fingerprint builds the index, every later call (either entry point,
    any number of sample draws) reuses it.
    """

    def __init__(self, db: Database, *, rep: str = "usr",
                 policy: Optional[CapacityPolicy] = None,
                 max_plans: int = 64):
        if rep not in ("csr", "usr", "both"):
            raise ValueError(f"rep must be csr|usr|both, got {rep!r}")
        self.db = db
        self.rep = rep
        self.policy = policy or DEFAULT_POLICY
        self.max_plans = max_plans
        self.stats = CacheStats()
        self._shreds: "collections.OrderedDict[Tuple, _IndexEntry]" = collections.OrderedDict()
        self._plans: "collections.OrderedDict[Tuple, CompiledPlan]" = collections.OrderedDict()
        # Shard-planner verdicts (tiny; root size + mesh shape + policy only
        # change when the bound snapshot moves — apply_delta drops verdicts
        # whose root relation was touched, rebind() drops them all). Values
        # are (ShardPlan, root relation name).
        self._shard_verdicts: "collections.OrderedDict[Tuple, object]" = collections.OrderedDict()

    # -- cache plumbing ------------------------------------------------------
    def _shred_for(self, query: JoinQuery, rep: str) -> Shred:
        key = plan_key(query, rep, self.db.version)
        hit = self._shreds.get(key)
        if hit is not None:
            self._shreds.move_to_end(key)
            self.stats.shred_hits += 1
            return hit.index
        self.stats.shred_builds += 1
        shred = build_shred(self.db, query, rep=rep)
        self._shreds[key] = _IndexEntry(shred, query, self.db.version)
        while len(self._shreds) > self.max_plans:
            self._shreds.popitem(last=False)
        return shred

    def _stacked_shred_for(self, query: JoinQuery, rep: str, mesh,
                           sp: ShardPlan) -> StackedShred:
        """The stacked per-shard index for a sharded plan; lives in the same
        LRU as single-device shreds under a mesh-extended key, placed one
        shard per device along the plan's shard axes."""
        num_shards = sp.num_shards
        key = sharded_plan_key(query, rep, mesh, num_shards, self.db.version)
        hit = self._shreds.get(key)
        if hit is not None:
            self._shreds.move_to_end(key)
            self.stats.shred_hits += 1
            return hit.index
        self.stats.shred_builds += 1
        stacked, base = build_stacked(self.db, query, num_shards, rep=rep)
        stacked = place_stacked(
            stacked, NamedSharding(auto_mesh(mesh), P(sp.axes)))
        self._shreds[key] = _IndexEntry(stacked, query, self.db.version,
                                        base=base, num_shards=num_shards)
        while len(self._shreds) > self.max_plans:
            self._shreds.popitem(last=False)
        return stacked

    @staticmethod
    def _resolve_spec(spec: Optional[DrawSpec], **kw) -> DrawSpec:
        """The single normalization shim behind every entry point
        (DESIGN.md §13): start from ``spec`` (or an empty ``DrawSpec``)
        and overlay each legacy kwarg that was explicitly passed. Kwargs
        win over spec fields; ``None`` means "not passed"."""
        return merge_spec(spec, **kw)

    def compile(self, query: JoinQuery, spec: Optional[DrawSpec] = None, *,
                rep: Optional[str] = None,
                method: Optional[str] = None,
                project: Optional[tuple] = None,
                narrow: Optional[bool] = None,
                kernels: Optional[str] = None) -> CompiledPlan:
        """Plan + index + jit for a query; cached by fingerprint.

        ``spec`` (or the equivalent legacy kwargs — see ``DrawSpec``):
        ``project`` is the bag-based projection attributes A for queries of
        the paper's form beta_y(pi_A(Q^)) (eq. 2). Sampling first and
        projecting the sample is exact for bag projection; set-based
        free-connex projection is out of scope (DESIGN.md §9).
        """
        spec = self._resolve_spec(
            spec, rep=rep, method=method,
            project=tuple(project) if project else None, narrow=narrow,
            kernels=kernels)
        crep = spec.rep or self.rep
        if spec.project is not None and query.prob_var is not None \
                and query.prob_var not in spec.project:
            raise ValueError("prob_var (y) must be in the projection A")
        key = executor_key(query, crep, spec.method, spec.project,
                           self.db.version, spec.narrow, spec.kernels)
        hit = self._plans.get(key)
        if hit is not None:
            self._plans.move_to_end(key)
            self.stats.plan_hits += 1
            return hit
        self.stats.plan_misses += 1
        plan = CompiledPlan(
            query=query, spec=spec.plan_view(crep),
            shred=self._shred_for(query, crep), policy=self.policy,
        )
        self._plans[key] = plan
        while len(self._plans) > self.max_plans:
            self._plans.popitem(last=False)
        return plan

    def compile_sharded(self, query: JoinQuery, mesh,
                        spec: Optional[DrawSpec] = None, *,
                        axes: Optional[tuple] = None,
                        rep: Optional[str] = None,
                        method: Optional[str] = None,
                        project: Optional[tuple] = None,
                        narrow: Optional[bool] = None,
                        kernels: Optional[str] = None,
                        ) -> Union[CompiledPlan, ShardedPlan]:
        """Plan + stacked index + shard_map jit for a query over ``mesh``.

        The shard planner picks the partition axes/count from the mesh
        shape, the root relation size, and the engine's ``CapacityPolicy``
        (pass ``axes`` to pin them). Degenerate plans (one shard, no axes)
        transparently fall back to the single-device ``CompiledPlan`` — a
        1-device mesh costs nothing over not passing one (DESIGN.md §8).
        """
        spec = self._resolve_spec(
            spec, rep=rep, method=method,
            project=tuple(project) if project else None, narrow=narrow,
            kernels=kernels,
            axes=tuple(axes) if axes is not None else None)
        crep = spec.rep or self.rep
        fp = query_fingerprint(query)
        vkey = (fp, mesh_fingerprint(mesh), spec.axes)
        hit = self._shard_verdicts.get(vkey)
        if hit is None:  # GYO + planner only on the first sighting
            root_atom = build_plan(query).atom
            root_rows = self.db.relations[root_atom.relation].num_rows
            sp = plan_shards(mesh, root_rows, self.policy, axes=spec.axes)
            self._shard_verdicts[vkey] = (sp, root_atom.relation)
            while len(self._shard_verdicts) > self.max_plans:
                self._shard_verdicts.popitem(last=False)
        else:
            sp, _root = hit
        if not sp.axes:
            return self.compile(query, spec)
        key = sharded_executor_key(query, crep, spec.method, spec.project,
                                   mesh, sp.axes, self.db.version,
                                   spec.narrow, spec.kernels)
        hit = self._plans.get(key)
        if hit is not None:
            self._plans.move_to_end(key)
            self.stats.plan_hits += 1
            return hit
        self.stats.plan_misses += 1
        plan = ShardedPlan(
            query=query, spec=spec.plan_view(crep),
            mesh=mesh, axes=sp.axes,
            stacked=self._stacked_shred_for(query, crep, mesh, sp),
            policy=self.policy,
        )
        self._plans[key] = plan
        while len(self._plans) > self.max_plans:
            self._plans.popitem(last=False)
        return plan

    def rebind(self, db: Database) -> "QueryEngine":
        """Bind a new database instance, dropping both caches. Always
        invalidates — even an identical schema fingerprint can carry
        different data values, and shreds depend on values (cheap
        correctness over cleverness; see DESIGN.md §7). For *derived*
        snapshots, ``apply_delta`` keeps the caches warm instead."""
        self.db = db
        self._shreds.clear()
        self._plans.clear()
        self._shard_verdicts.clear()  # root sizes may differ
        return self

    def apply_delta(self, delta) -> "QueryEngine":
        """Advance the bound snapshot to ``self.db.apply(delta)`` and
        *upgrade* every warm cache entry instead of dropping it
        (DESIGN.md §11).

        Single-device shreds touched by the delta are merged forward via
        ``reshred_incremental`` (bit-identical to a rebuild, at delta
        cost); stacked shreds are re-partitioned with per-shard reuse
        (``shards_reused``/``shards_rebuilt`` in ``CacheStats``); compiled
        plans keep their jitted executors, so a shape-preserving delta
        costs zero retraces on the next warm draw. Entries for queries the
        delta does not touch are re-keyed to the new version for free.
        ``rebind`` remains the full-invalidation escape hatch.
        """
        old_db = self.db
        new_db = old_db.apply(delta)
        new_v = new_db.version
        touched = set(delta.touched())

        upgraded: Dict[Tuple, object] = {}  # key sans version -> new index
        new_shreds: "collections.OrderedDict[Tuple, _IndexEntry]" = \
            collections.OrderedDict()
        for key, entry in self._shreds.items():
            qrels = {a.relation for a in entry.query.atoms}
            if not (touched & qrels):
                new_entry = dataclasses.replace(entry, version=new_v)
            elif isinstance(entry.index, StackedShred):
                stacked, base, reused, rebuilt = reshard_incremental(
                    entry.index, entry.base, new_db, entry.query,
                    entry.num_shards, rep=key[1])
                self.stats.shred_upgrades += 1
                self.stats.shards_reused += reused
                self.stats.shards_rebuilt += rebuilt
                new_entry = _IndexEntry(stacked, entry.query, new_v,
                                        base=base,
                                        num_shards=entry.num_shards)
            else:
                shred = reshred_incremental(entry.index, old_db,
                                            entry.query, delta)
                self.stats.shred_upgrades += 1
                new_entry = _IndexEntry(shred, entry.query, new_v)
            upgraded[key[:-1]] = new_entry.index
            new_shreds[key[:-1] + (new_v,)] = new_entry
        self._shreds = new_shreds

        new_plans: "collections.OrderedDict[Tuple, CompiledPlan]" = \
            collections.OrderedDict()
        for key, plan in self._plans.items():
            qrels = {a.relation for a in plan.query.atoms}
            if touched & qrels:
                if isinstance(plan, ShardedPlan):
                    skey = sharded_plan_key(plan.query, key[1], plan.mesh,
                                            plan.num_shards)[:-1]
                    stacked = upgraded.get(skey)
                    if stacked is None:
                        # Orphaned sharded plan (its stacked index fell out
                        # of the LRU): no base to diff against — drop it.
                        continue
                    plan.rebind_stacked(stacked)
                else:
                    skey = plan_key(plan.query, key[1])[:-1]
                    shred = upgraded.get(skey)
                    if shred is None:  # orphan: upgrade from its own index
                        shred = reshred_incremental(plan.shred, old_db,
                                                    plan.query, delta)
                        self.stats.shred_upgrades += 1
                    plan.rebind_shred(shred)
                self.stats.plan_upgrades += 1
            new_plans[key[:-1] + (new_v,)] = plan
        self._plans = new_plans

        # Shard-planner verdicts keyed off a touched root relation are
        # stale (the root row count may have moved); recompute lazily.
        for vkey in [k for k, (_, root) in self._shard_verdicts.items()
                     if root in touched]:
            del self._shard_verdicts[vkey]

        self.db = new_db
        return self

    # -- entry points --------------------------------------------------------
    def full_join(self, query: JoinQuery, spec: Optional[DrawSpec] = None, *,
                  rep: Optional[str] = None,
                  mesh=None, axes: Optional[tuple] = None,
                  ) -> Dict[str, jnp.ndarray]:
        """Yannakakis full join via the cached index (SYA; Prop 4.4/4.5).

        With a mesh (``spec.mesh`` or ``mesh=``), the root is
        block-partitioned over the mesh's data axes and each shard flattens
        its block through the stacked index; the gathered result is
        bit-identical to the single-device path, order included
        (DESIGN.md §8)."""
        spec = self._resolve_spec(spec, rep=rep, mesh=mesh,
                                  axes=tuple(axes) if axes is not None
                                  else None)
        if spec.mesh is not None:
            plan = self.compile_sharded(query, spec.mesh, spec)
            if isinstance(plan, ShardedPlan):
                return plan.full_join()
        else:
            plan = self.compile(query, spec)
        return plan.full_join(rep=spec.rep)

    def poisson_sample(self, query: JoinQuery, key,
                       spec: Optional[DrawSpec] = None, *,
                       cap: Optional[int] = None, acap: Optional[int] = None,
                       rep: Optional[str] = None,
                       method: Optional[str] = None,
                       project: Optional[tuple] = None,
                       narrow: Optional[bool] = None,
                       kernels: Optional[str] = None,
                       auto: bool = False, mesh=None,
                       axes: Optional[tuple] = None) -> JoinSample:
        """One independent Poisson sample of ``beta_y(Q)`` via the cached
        index. ``auto=True`` applies the policy's redraw-on-overflow loop.
        ``spec=`` carries the full draw configuration (``DrawSpec``); the
        legacy kwargs keep working and win field-by-field over the spec.

        With a mesh, per-shard trials run under device-folded keys and
        one psum reports the global count — distributionally identical to
        the global draw, and bit-reproducible against a host loop folding
        the shard index into the same base key (DESIGN.md §8). Degenerate
        meshes fall back to the single-device plan transparently."""
        spec = self._resolve_spec(
            spec, cap=cap, acap=acap, rep=rep, method=method,
            project=tuple(project) if project else None, narrow=narrow,
            kernels=kernels,
            mesh=mesh, axes=tuple(axes) if axes is not None else None)
        if query.prob_var is None:
            raise ValueError("Poisson sampling needs query.prob_var (beta_y)")
        if spec.mesh is not None:
            plan = self.compile_sharded(query, spec.mesh, spec)
            if isinstance(plan, ShardedPlan):
                if auto:
                    return plan.sample_auto(key, cap=spec.cap, acap=spec.acap)
                return plan.sample(key, cap=spec.cap, acap=spec.acap)
            # degenerate mesh: compile_sharded already fell back to the
            # single-device CompiledPlan — reuse it, don't compile twice
        else:
            plan = self.compile(query, spec)
        if auto:
            return plan.sample_auto(key, cap=spec.cap, acap=spec.acap)
        return plan.sample(key, cap=spec.cap, acap=spec.acap,
                           rep=spec.rep if spec.rep != "both" else None)

    # ``sample`` is the preferred name for the Poisson entry point; kwargs
    # (including ``spec=`` and ``mesh=``) are identical.
    sample = poisson_sample

    def sample_batch(self, query: JoinQuery, keys,
                     spec: Optional[DrawSpec] = None, *,
                     cap: Optional[int] = None, acap: Optional[int] = None,
                     rep: Optional[str] = None,
                     method: Optional[str] = None,
                     project: Optional[tuple] = None,
                     narrow: Optional[bool] = None,
                     kernels: Optional[str] = None, mesh=None,
                     axes: Optional[tuple] = None) -> JoinSample:
        """``B`` independent Poisson draws of ``beta_y(Q)`` in one dispatch
        (DESIGN.md §10). ``keys`` is a ``(B,)`` PRNG key vector — pass
        ``jax.random.split(key, B)`` for the canonical stream. The result's
        leaves carry a leading batch axis (columns/positions ``(B, cap)``,
        count/overflow ``(B,)``) and lane ``b`` is bit-identical to
        ``sample(query, keys[b])`` with the same spec/kwargs.

        The plan is the *same* cache entry the single-draw path uses (one
        fingerprint, one shred, one ``CompiledPlan``), so interleaving
        single and batched draws rebuilds nothing; batch sizes are bucketed
        to powers of two, so warm same-bucket batches never retrace. With
        a mesh, the sharded plan composes: shard_map outside, vmap
        inside, one psum for the ``(B,)`` global counts.
        """
        spec = self._resolve_spec(
            spec, cap=cap, acap=acap, rep=rep, method=method,
            project=tuple(project) if project else None, narrow=narrow,
            kernels=kernels,
            mesh=mesh, axes=tuple(axes) if axes is not None else None)
        if query.prob_var is None:
            raise ValueError("Poisson sampling needs query.prob_var (beta_y)")
        if spec.mesh is not None:
            plan = self.compile_sharded(query, spec.mesh, spec)
            if isinstance(plan, ShardedPlan):
                return plan.sample_batch(keys, cap=spec.cap, acap=spec.acap)
            # degenerate mesh: fall through to the single-device plan
        else:
            plan = self.compile(query, spec)
        return plan.sample_batch(keys, cap=spec.cap, acap=spec.acap,
                                 rep=spec.rep if spec.rep != "both" else None)

    def uniform_sample(self, query: JoinQuery, key, p: float, *,
                       spec: Optional[DrawSpec] = None,
                       cap: Optional[int] = None, method: str = "hybrid",
                       rep: Optional[str] = None) -> JoinSample:
        """beta_p with one fixed probability for every join tuple (§6.1).

        ``method`` here selects the *position* sampler (hybrid/bern/geo/
        binom) — it is unrelated to ``DrawSpec.method``, so a ``spec``
        contributes only ``rep``/``cap``/``narrow`` on this path."""
        spec = self._resolve_spec(spec, cap=cap, rep=rep)
        plan = self.compile(query, rep=spec.rep, narrow=spec.narrow)
        return plan.uniform_sample(key, p, cap=spec.cap, method=method)

    def join_size(self, query: JoinQuery) -> int:
        """|Q(db)| in O(1) from the cached index (never materialized)."""
        return self.compile(query).join_size

    def cache_info(self) -> Dict[str, object]:
        """Staleness-observable cache state (DESIGN.md §11): the bound
        snapshot version plus every cache entry's version. Serve's stats
        path reports this, and tests assert entries never trail the bound
        version after ``apply_delta``."""
        return {
            "db_version": self.db.version,
            "shreds": [
                {"fingerprint": k[0], "rep": k[1], "version": e.version,
                 "stacked": isinstance(e.index, StackedShred)}
                for k, e in self._shreds.items()
            ],
            "plans": [
                {"fingerprint": k[0], "rep": k[1], "version": k[-1],
                 "sharded": isinstance(p, ShardedPlan)}
                for k, p in self._plans.items()
            ],
        }

    def explain(self, query: JoinQuery, *, rep: Optional[str] = None) -> str:
        """Human-readable plan: the (rerooted) join tree + cache state,
        including the bound snapshot version and per-entry cache versions
        (staleness is observable, not inferred — DESIGN.md §11)."""
        plan = self.compile(query, rep=rep)
        tree = build_plan(query)  # the rerooted tree the plan executes
        pol = config.current_policy()
        lines = [
            f"QueryEngine plan  rep={plan.rep}  method={plan.method}",
            f"  routes: get={plan.rep_default} draw={plan.route} "
            f"narrow={plan.narrow}  kernels "
            f"{'interpreted' if pol.interpret else 'compiled'} on "
            f"{config.backend()}",
            "  join tree (GYO):",
        ]
        lines += ["    " + l for l in tree.pretty().rstrip().split("\n")]
        info = self.cache_info()
        fp = query_fingerprint(query)
        entry_vs = sorted({e["version"] for e in
                           info["shreds"] + info["plans"]
                           if e["fingerprint"] == fp})
        lines += [
            f"  |Q(db)| = {plan.join_size}",
            f"  db version={info['db_version']}  "
            f"entry versions={entry_vs or [info['db_version']]}",
            f"  cached shreds={len(self._shreds)} plans={len(self._plans)} "
            f"(hits: shred={self.stats.shred_hits} plan={self.stats.plan_hits}"
            f"; upgrades: shred={self.stats.shred_upgrades} "
            f"plan={self.stats.plan_upgrades})",
        ]
        return "\n".join(lines)

    # -- baselines (kept for benchmarks; not cached) -------------------------
    def materialize_and_scan(self, key, query: JoinQuery,
                             uniform_p: Optional[float] = None):
        """The M&S baseline: end-to-end materialize-then-Bernoulli, which
        deliberately bypasses the engine caches — it rebuilds its index per
        call, exactly the naive cost the I&P plans are measured against."""
        return yannakakis.materialize_and_scan(
            key, self.db, query, uniform_p=uniform_p, rep=self.rep)
