"""Distributed Poisson sampling: root partitioning and stacked-index build.

Why Poisson sampling scales embarrassingly well (and fixed-size sampling
does not): the join result is the disjoint union of the joins produced by
any partition of the ROOT relation's rows, and Poisson trials are
independent per tuple. So block-partitioning the root across devices and
sampling each block independently (with a device-folded PRNG key) is
*distributionally identical* to sampling globally — no coordination, no
rejection, one psum to report the global count. A fixed-k sampler would
instead need a global multivariate-hypergeometric split of k across shards.

This module is the *library* layer the engine's sharded path consumes
(DESIGN.md §8):

  * ``semijoin_filter``     — top-down pre-filter bounding the replicated
                              child relations by the root's join keys;
  * ``partition_root``      — block-partition the root with padding
                              (pad rows are weight-neutralized downstream);
  * ``build_stacked_shred`` — per-shard shredded indexes, all identical
                              shapes, stacked into one pytree with a
                              leading shard axis;
  * ``fold_shard_key``      — the device-folded PRNG key scheme.

``ShardedPoissonSampler`` is kept as a thin facade over
``repro.engine.sharding.ShardedPlan`` (the shard_map executors live there),
mirroring how ``core.PoissonSampler`` facades the single-device engine.
``launch/dryrun.py`` uses it for the paper's architecture on the
production meshes.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding

from .database import Database
from .jointree import JoinQuery, JoinTreeNode
from .relations import Relation, numeric_column, take_column
from .shred import (Shred, _dense_gids_np, _np_i64, build_plan, build_shred,
                    pack_index, root_prefix)

__all__ = [
    "RootPartition", "StackedShred", "ShardedPoissonSampler",
    "partition_root", "semijoin_filter", "build_stacked_shred",
    "build_stacked", "reshard_incremental", "fold_shard_key",
    "place_stacked",
]

I64 = jnp.int64


def fold_shard_key(key, axes: Tuple[str, ...]):
    """Device-distinct PRNG key inside shard_map: fold the linearized shard
    coordinate into ``key``. Shard ``s`` of the stacked index lands on the
    device with linearized coordinate ``s`` (P(axes) block layout), so a
    host-side loop over ``fold_in(key, s)`` reproduces the per-device keys
    bit-for-bit — the reproducibility contract tests and the engine's
    sharded path both rely on."""
    idx = jnp.zeros((), jnp.int32)
    for a in axes:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return jax.random.fold_in(key, idx)


def semijoin_filter(db: Database, query: JoinQuery) -> Database:
    """Top-down semijoin pre-filter: drop child rows that cannot join.

    Walks the (rerooted) join tree from the root, keeping in each child
    relation only the rows whose join key occurs in the parent's (already
    filtered) instance. A relation referenced by several atoms (self joins)
    keeps the union of the rows any alias needs. The root relation is never
    filtered — it is the partitioned side.

    Only dangling rows are removed, and the shred build retains dangling
    tuples with weight 0 anyway, so the join result *and every flat
    position* are unchanged (DESIGN.md §8); the filter just bounds the
    replicated child relations by the root's keys before the per-shard
    index builds.
    """
    plan = build_plan(query)
    keep: Dict[str, np.ndarray] = {}

    def visit(tnode: JoinTreeNode, parent_inst: Optional[Relation]) -> None:
        inst = db.instance_for(tnode.atom)
        if parent_inst is not None:
            shared = sorted(set(parent_inst.attrs) & set(inst.attrs))
            if shared and inst.num_rows and parent_inst.num_rows:
                kp, kc = _dense_gids_np(
                    [_np_i64(parent_inst.column(v)) for v in shared],
                    [_np_i64(inst.column(v)) for v in shared])
                mask = np.isin(kc, kp)
            else:  # cross product (or empty side): nothing to prune
                mask = np.ones((inst.num_rows,), bool)
            name = tnode.atom.relation
            keep[name] = mask if name not in keep else (keep[name] | mask)
            inst = inst.take(jnp.asarray(np.flatnonzero(mask)))
        for c in tnode.children:
            visit(c, inst)

    visit(plan, None)
    keep.pop(plan.atom.relation, None)  # the root is partitioned, not filtered
    rels = dict(db.relations)
    for name, mask in keep.items():
        rels[name] = db.relations[name].take(jnp.asarray(np.flatnonzero(mask)))
    return Database(rels, db.schemas)


@dataclasses.dataclass(frozen=True)
class RootPartition:
    """A block partition of the root relation into equal-sized shard dbs.

    ``shards[s]`` holds rows [s*rows_per_shard, (s+1)*rows_per_shard) of the
    root (short tail shards padded by repeating the last row); children are
    shared across shards. ``valid[s]`` counts the unpadded rows — the
    stacked build weight-neutralizes everything beyond it.
    """

    shards: Tuple[Database, ...]
    root_name: str
    rows_per_shard: int
    valid: Tuple[int, ...]


def partition_root(
    db: Database, query: JoinQuery, num_shards: int
) -> RootPartition:
    """Split the database into ``num_shards`` copies whose root-relation rows
    block-partition the original. Pad rows repeat the last row and get a
    zero probability when the query has a ``prob_var``; the stacked build
    additionally zeroes their weights, so pads contribute to neither
    samples nor full joins."""
    plan = build_plan(query)
    root_atom = plan.atom
    root_rel = db.relations[root_atom.relation]
    n = root_rel.num_rows
    per = -(-n // num_shards)  # 0 rows -> every shard empty (nothing to pad)
    prob_col = None
    if query.prob_var is not None:
        schema = db.schemas[root_atom.relation]
        for c, v in zip(schema, root_atom.variables):
            if v == query.prob_var:
                prob_col = c
    shards, valid = [], []
    for s in range(num_shards):
        lo, hi = min(s * per, n), min((s + 1) * per, n)
        idx = np.arange(lo, hi)
        if hi - lo < per:  # pad with last row, neutralized via p = 0 + w = 0
            idx = np.concatenate([idx, np.full(per - (hi - lo), max(n - 1, 0))])
        cols = {}
        for c, v in root_rel.columns.items():
            col = take_column(v, jnp.asarray(idx))
            if c == prob_col and hi - lo < per:
                col = col.at[hi - lo:].set(0)
            cols[c] = col
        rels = dict(db.relations)
        rels[root_atom.relation] = Relation(cols)
        shards.append(Database(rels, db.schemas))
        valid.append(hi - lo)
    return RootPartition(tuple(shards), root_atom.relation, per, tuple(valid))


@dataclasses.dataclass
class StackedShred:
    """Per-shard shred indexes stacked into one pytree (leading dim S).

    This is what the engine's shred cache holds for a sharded plan, keyed
    by (query fingerprint, rep, mesh shape, shard count) — DESIGN.md §8.
    Pad rows carry weight 0, so ``prefE[s, -1]`` is the true per-shard join
    size and the shard flattens concatenate to exactly the global flatten.
    """

    shred: Shred                  # every leaf has a leading shard axis
    w: jnp.ndarray                # (S, n_root) int64 root weights, pads zeroed
    p: Optional[jnp.ndarray]      # (S, n_root) root probabilities, or None
    prefE: jnp.ndarray            # (S, n_root + 1) exclusive weight prefixes
    num_shards: int
    root_name: str
    valid: Tuple[int, ...]        # unpadded root rows per shard
    join_sizes: Tuple[int, ...]   # concrete per-shard |Q_s(db)|

    @property
    def join_size(self) -> int:
        """|Q(db)| — the shard join sizes sum to the global size exactly."""
        return int(sum(self.join_sizes))


def _build_one_shard(sdb: Database, query: JoinQuery, rep: str,
                     valid: int) -> Shred:
    """One shard's shred with pad rows weight-neutralized post-build."""
    sh = build_shred(sdb, query, rep=rep)
    n = sh.root.num_rows
    if valid < n:
        w = np.where(np.arange(n) < valid, np.asarray(sh.root.weight), 0)
        root = dataclasses.replace(sh.root, weight=jnp.asarray(w))
        prefE = root_prefix(w)
        # Re-pack the fused-GET arena: it embeds root_prefE (DESIGN.md §4).
        packed, paged = pack_index(root, prefE)
        sh = Shred(root=root, root_prefE=prefE, rep=sh.rep,
                   packed=packed, paged=paged)
    return sh


def _stack_shards(built, part: RootPartition, query: JoinQuery,
                  num_shards: int) -> StackedShred:
    """Stack per-shard shreds (identical pytree shapes) into one pytree.

    The fused-GET arena (``Shred.packed``) stacks like any other leaf, but
    only when *every* shard packed one with the same layout — int32
    narrowing is per-shard, and a mixed verdict would be a treedef
    mismatch. Otherwise the stack drops the arenas and the sharded
    executors take the per-node path (the documented fallback ladder,
    DESIGN.md §4/§9)."""
    layouts = {(None if b.packed is None else b.packed.layout,
                None if b.paged is None else b.paged.layout) for b in built}
    if layouts != {(None, None)} and len(layouts) > 1:
        built = [dataclasses.replace(b, packed=None, paged=None)
                 for b in built]
    shred = jax.tree.map(lambda *xs: jnp.stack(xs), *built)
    w = jnp.stack([b.root.weight for b in built])
    pvar = query.prob_var
    # The samplers read p as a number (a float64 column is decoded from
    # its bit patterns on the host), as the single-device plan binds it.
    p = (jnp.stack([numeric_column(b.root.data.column(pvar)) for b in built])
         if pvar is not None else None)
    prefE = jnp.stack([b.root_prefE for b in built])
    return StackedShred(
        shred=shred, w=w, p=p, prefE=prefE, num_shards=num_shards,
        root_name=part.root_name, valid=part.valid,
        join_sizes=tuple(int(b.root_prefE[-1]) for b in built),
    )


def place_stacked(stacked: StackedShred, sharding) -> StackedShred:
    """``stacked`` with every leaf of its index, weights, probabilities and
    prefixes placed under ``sharding`` — a ``NamedSharding`` over the
    shard axes puts shard s on the mesh's device s. Leaves already placed
    that way are not copied."""
    put = partial(jax.device_put, device=sharding)
    return dataclasses.replace(
        stacked, shred=jax.tree.map(put, stacked.shred),
        w=put(stacked.w), p=None if stacked.p is None else put(stacked.p),
        prefE=put(stacked.prefE))


def build_stacked(
    db: Database, query: JoinQuery, num_shards: int, rep: str = "usr",
    prefilter: bool = True,
) -> Tuple[StackedShred, Database]:
    """Build ``num_shards`` identical-shape shred indexes and stack them;
    also returns the (semijoin-filtered) base database the shards were cut
    from — the anchor ``reshard_incremental`` diffs against (DESIGN.md §11).

    Children are semijoin-prefiltered once (shared by all shards), the root
    is block-partitioned, and pad rows are weight-zeroed post-build so they
    are invisible to sampling *and* flattening. All shards share one pytree
    structure, so the stack is shard_map-able with in_specs P(axes) on the
    leading dimension.
    """
    base = semijoin_filter(db, query) if prefilter else db
    part = partition_root(base, query, num_shards)
    built = [_build_one_shard(sdb, query, rep, part.valid[s])
             for s, sdb in enumerate(part.shards)]
    return _stack_shards(built, part, query, num_shards), base


def build_stacked_shred(
    db: Database, query: JoinQuery, num_shards: int, rep: str = "usr",
    prefilter: bool = True,
) -> StackedShred:
    """``build_stacked`` without the base handle (API-stable entry point)."""
    return build_stacked(db, query, num_shards, rep=rep,
                         prefilter=prefilter)[0]


def _relations_equal(a, b) -> bool:
    """Value equality of two relations (column names, dtypes, data)."""
    if set(a.columns) != set(b.columns):
        return False
    for c in a.columns:
        x, y = a.columns[c], b.columns[c]
        if x is not y and (
                x.dtype != y.dtype or x.shape != y.shape
                or type(x) is not type(y)
                or not bool(jnp.array_equal(*jax.tree.leaves((x, y))))):
            return False
    return True


def reshard_incremental(
    stacked: StackedShred, base: Database, db_new: Database,
    query: JoinQuery, num_shards: int, rep: str = "usr",
) -> Tuple[StackedShred, Database, int, int]:
    """Advance a stacked index to a new snapshot, re-building only shards
    whose inputs changed (DESIGN.md §11).

    ``base`` is the filtered base ``build_stacked`` returned for the old
    snapshot. The new snapshot is re-filtered and re-partitioned (linear
    scans; the expensive part of a shard build is the per-shard sort-based
    grouping, which is what reuse avoids); a shard is reused verbatim when
    every child relation and its slice of the root block are value-equal.
    Deltas that shift the root partition (row-count changes) or touch the
    filtered children rebuild the affected shards — bit-identical to a
    from-scratch ``build_stacked`` either way.

    The new stack keeps the old one's placement (``place_stacked``).

    Returns ``(stacked_new, base_new, shards_reused, shards_rebuilt)``.
    """
    base_new = semijoin_filter(db_new, query)
    part_new = partition_root(base_new, query, num_shards)
    root_atom = build_plan(query).atom
    # Only the query's own child relations feed the per-shard builds: a
    # delta that also touches unrelated relations (other tenants' tables)
    # must not defeat shard reuse.
    child_rels = {a.relation for a in query.atoms} - {stacked.root_name}
    children_same = (num_shards == stacked.num_shards) and all(
        _relations_equal(base.relations[name], base_new.relations[name])
        for name in child_rels)

    built, reused = [], 0
    old_root_data = stacked.shred.root.data  # columns have leading shard dim
    for s, sdb in enumerate(part_new.shards):
        can_reuse = (
            children_same
            and part_new.valid[s] == stacked.valid[s]
            and _relations_equal(
                sdb.instance_for(root_atom),
                Relation({v: col[s]
                          for v, col in old_root_data.columns.items()}))
        )
        if can_reuse:  # slice the full per-shard tree only for actual reuse
            sh = jax.tree.map(lambda x, s=s: x[s], stacked.shred)
            if sh.packed is None and sh.paged is None:
                # The stack may have dropped the arenas (a mixed per-shard
                # narrowing verdict in an earlier epoch); re-pack so a reused
                # shard carries exactly what a from-scratch build would —
                # otherwise packed=None would propagate through every future
                # reuse and the fused/paged path would be lost until a rebind.
                packed, paged = pack_index(sh.root, sh.root_prefE)
                sh = dataclasses.replace(sh, packed=packed, paged=paged)
            built.append(sh)
            reused += 1
        else:
            built.append(_build_one_shard(sdb, query, rep, part_new.valid[s]))
    stacked_new = _stack_shards(built, part_new, query, num_shards)
    if isinstance(stacked.w.sharding, NamedSharding):
        stacked_new = place_stacked(stacked_new, stacked.w.sharding)
    return stacked_new, base_new, reused, num_shards - reused


class ShardedPoissonSampler:
    """Data-parallel Poisson sampling over a device mesh.

    Facade over the engine's sharded path (``repro.engine.sharding``): one
    stacked index, shard_map'd per-step sampling with device-folded keys.
    Kept for API stability and the dry-run entry; new code should call
    ``QueryEngine.sample(..., mesh=...)`` so indexes are cached across
    queries (DESIGN.md §8).
    """

    def __init__(
        self,
        db: Database,
        query: JoinQuery,
        mesh: Mesh,
        axes: Tuple[str, ...] = ("data",),
        rep: str = "usr",
        method: str = "exprace",
    ):
        # Lazy: repro.engine imports repro.core (same pattern as poisson.py).
        from repro.engine import QueryEngine

        self.mesh = mesh
        self.axes = axes
        self.rep = "usr" if rep == "both" else rep
        self.method = method
        self.engine = QueryEngine(db, rep=rep)
        self._plan = self.engine.compile_sharded(
            query, mesh, axes=axes, rep=rep, method=method)
        self.num_shards = self._plan.num_shards
        self.root_name = self._plan.stacked.root_name
        self.shred = self._plan.stacked.shred
        self.w = self._plan.stacked.w
        self.p = self._plan.stacked.p
        self.prefE = self._plan.stacked.prefE
        self.cap = self._plan.cap
        self.acap = self._plan.acap

    def sample_step(self, key):
        """One independent global Poisson sample. Returns the sharded
        JoinSample (leading dim = shards) and the global count."""
        return self._plan.sample_step(key)

    # -- dry-run support -----------------------------------------------------
    def lower_step(self):
        return self._plan.lower_step()
