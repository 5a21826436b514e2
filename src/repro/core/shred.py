"""Shredded random-access index construction (paper §4).

Builds the chained (CSR) and/or unchained (USR) shredded representation of
the 2NSA expression ``mu*(E)`` derived from a join tree, in O(|db| log |db|)
(one argsort per tree edge — the TPU-native replacement for the paper's O(|db|)
hash grouping; see DESIGN.md §3).

Semantics note (zero-weight retention): dangling tuples are *kept* with
weight 0 instead of being compacted away. The flatten order and prefix
vectors are unaffected (a zero-weight tuple produces no flat tuples), which
keeps every shape static under jit while preserving the paper's semantics
exactly. The bottom-up weight product implements the semijoin reduction of
the nested-semijoin build: a root tuple's weight is exactly the number of
join tuples extending it.

Canonical flatten order: root tuples in physical order; within a nested
attribute, tuples in join-key-sorted (stable) order; combinations in the
paper's mixed-radix order (eq. 6-7, first child least significant). CSR and
USR share this order, so their GETs agree tuple-for-tuple.

Incremental maintenance (DESIGN.md §11): the build is split into reusable
passes (edge keys -> sorted group -> link columns), and
``reshred_incremental`` merges a ``DeltaBatch`` into an existing shred —
sorting only the delta and re-deriving the affected link columns — with the
contract that the result is bit-identical to a from-scratch
``build_shred(db.apply(delta), query, rep)``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels import ops as _kops

from .database import Database
from .jointree import Atom, JoinQuery, JoinTreeNode, gyo_join_tree, reroot_for
from .relations import Relation, place_column

__all__ = ["ShredNode", "Shred", "build_shred", "build_plan",
           "reshred_incremental", "PackedShred", "PagedArena", "ArenaLayout",
           "ArenaEdge", "pack_arena", "pack_index", "root_prefix",
           "MAX_JOIN"]

I64 = jnp.int64
I32 = jnp.int32
_I32_MAX = (1 << 31) - 1
# Largest supported join size: GET splits flat offsets exactly through
# float64 (probe._mixed_radix_split), and EXPRACE places arrivals on cells
# through a float64 mass line.
MAX_JOIN = 1 << 53


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ShredNode:
    """One Sigma(Y) of the shredded representation (a join-tree node).

    Arrays describing this node's rows:
      data      Relation over this node's variables (n rows).
      weight    (n,) int64 — flatten weight of the nested tuple at each row.
    Arrays describing this node's role as a *child* (grouped by parent key);
    absent (None) on the root:
      nxt       (n,) int32 CSR same-key chain in sorted order (-1 terminates;
                built for rep 'csr'/'both').
      perm      (n,) int32 sorted-order -> row id. Always built: USR-GET
                probes it, and incremental reshred merges deltas into it
                (DESIGN.md §11), so CSR indexes carry it too.
      cumw_excl (n+1,) int64 exclusive prefix of weights in sorted order
                (always built, same reasons).
    Per-child link columns (tuples aligned with ``children``):
      child_hd    (n,) int32 head row id in child (CSR).       -1 if empty.
      child_start (n,) int64 start offset into child's sorted order (USR).
      child_len   (n,) int32 run length in child's sorted order.
      child_w     (n,) int64 total weight of the joining child group.
    """

    name: str
    variables: Tuple[str, ...]
    owned: Tuple[str, ...]  # variables this node materializes in GET output
    data: Relation
    weight: jnp.ndarray
    children: Tuple["ShredNode", ...] = ()
    nxt: Optional[jnp.ndarray] = None
    perm: Optional[jnp.ndarray] = None
    cumw_excl: Optional[jnp.ndarray] = None
    child_hd: Tuple[jnp.ndarray, ...] = ()
    child_start: Tuple[jnp.ndarray, ...] = ()
    child_len: Tuple[jnp.ndarray, ...] = ()
    child_w: Tuple[jnp.ndarray, ...] = ()

    _ARRAY_FIELDS = ("data", "weight", "children", "nxt", "perm", "cumw_excl",
                     "child_hd", "child_start", "child_len", "child_w")

    def tree_flatten(self):
        leaves = tuple(getattr(self, f) for f in self._ARRAY_FIELDS)
        aux = (self.name, self.variables, self.owned)
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        name, variables, owned = aux
        return cls(name, variables, owned, *leaves)

    @property
    def num_rows(self) -> int:
        return self.weight.shape[0]

    def nodes(self) -> List["ShredNode"]:
        out = [self]
        for c in self.children:
            out.extend(c.nodes())
        return out


# ---------------------------------------------------------------------------
# Packed index arena (fused GET, DESIGN.md §4)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ArenaEdge:
    """Static arena addressing of one tree edge (all offsets are element
    indices into the flat int32 arena; baked into the fused kernel)."""

    parent: int    # output slot of the parent node
    slot: int      # output slot of the child node (pre-order)
    cs_off: int    # parent's child_start column for this edge (n_parent,)
    cw_off: int    # parent's child_w column for this edge (n_parent,)
    ce_off: int    # child's cumw_excl (n_child + 1,)
    perm_off: int  # child's perm (n_child,)
    n_child: int


@dataclasses.dataclass(frozen=True)
class ArenaLayout:
    """Hashable static layout of a packed arena: slot names (pre-order,
    slot 0 = root), root prefix length, and per-edge offsets. Passed as a
    static jit argument to ``kernels.tree_probe.tree_probe``."""

    names: Tuple[str, ...]
    n_root: int
    root_len: int  # n_root + 1 (root_prefE lives at offset 0)
    edges: Tuple[ArenaEdge, ...]
    size: int      # total arena length in int32 elements

    @property
    def num_slots(self) -> int:
        return len(self.names)

    def page_bounds(self) -> Tuple[Tuple[int, int], ...]:
        """Per-page ``(start, end)`` element ranges of the paged split
        (DESIGN.md §15): page 0 is the root prefix, page ``i+1`` is edge
        ``i``'s four columns — which ``pack_arena`` lays out consecutively
        (``child_start``/``child_w``/``cumw_excl``/``perm``), so every page
        is one contiguous slice of the monolithic arena and the pages
        concatenate back to it exactly."""
        return ((0, self.root_len),) + tuple(
            (e.cs_off, e.perm_off + e.n_child) for e in self.edges)

    @property
    def max_page(self) -> int:
        """Largest page in int32 elements — the VMEM working set of the
        paged probe (two double-buffered pages of this size), the quantity
        the paged rung gates against ``KernelPolicy.vmem_limit``."""
        return max(end - start for start, end in self.page_bounds())


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PackedShred:
    """The fused-GET index arena: every per-node table (``root_prefE``,
    ``child_start``, ``child_w``, ``cumw_excl``, ``perm``) narrowed to
    int32 and packed into ONE flat buffer + a static offset layout, so the
    fused tree-probe kernel keeps the whole index VMEM-resident across
    tree levels (DESIGN.md §4 "Fused GET"). Built iff every value fits
    int32 (join_size < 2^31 — the narrowing rule; otherwise the int64
    per-node path stands, DESIGN.md §9)."""

    arena: jnp.ndarray  # (size,) int32
    layout: ArenaLayout

    def tree_flatten(self):
        return (self.arena,), (self.layout,)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(leaves[0], aux[0])


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedArena:
    """The page-sliced sibling of ``PackedShred`` (DESIGN.md §15): the same
    int32 index, same ``ArenaLayout``, but held as one array per page
    (``layout.page_bounds()`` — root prefix, then one page per tree edge)
    instead of one monolithic buffer. Built when the arena exceeds the
    VMEM budget but every page fits it: the paged tree-probe streams the
    pages through VMEM (double-buffered DMA on TPU, one launch per page on
    GPU/CPU) instead of dropping to the ~4-9x-slower per-node path.

    Pages are contiguous slices of the monolithic arena, so all in-page
    offsets are the ``ArenaEdge`` offsets rebased by the page start —
    static arithmetic, no extra metadata."""

    pages: Tuple[jnp.ndarray, ...]  # per-page int32, sizes per page_bounds()
    layout: ArenaLayout

    def tree_flatten(self):
        return (self.pages,), (self.layout,)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(leaves[0], aux[0])

    @classmethod
    def from_packed(cls, packed: "PackedShred") -> "PagedArena":
        """Page-slice an existing monolithic arena (static bounds — traces
        cleanly, so a call-time policy with a shrunken ``vmem_limit`` can
        derive the paged view of an already-packed shred on the fly)."""
        pages = tuple(packed.arena[s:e]
                      for s, e in packed.layout.page_bounds())
        return cls(pages, packed.layout)


def _arena_pieces(root: "ShredNode", root_prefE: jnp.ndarray):
    """The shared packing walk: the arena's numpy pieces + its layout, or
    ``None`` when int32 narrowing is refused (an empty node — nothing to
    probe, callers guard ``join_size == 0`` anyway — or any value above
    int32 range, the documented int64 fallback, DESIGN.md §9).

    Piece order: ``root_prefE`` at offset 0, then per tree edge in the
    exact pre-order the per-node GET recurses (``probe._usr_sub``):
    ``child_start``, ``child_w``, ``cumw_excl``, ``perm``.
    """
    if any(nd.num_rows == 0 for nd in root.nodes()):
        return None
    pieces = [np.asarray(root_prefE)]
    names = [root.name]
    edges: List[ArenaEdge] = []
    off = pieces[0].shape[0]

    def walk(node: "ShredNode", parent_slot: int) -> None:
        nonlocal off
        for ci, child in enumerate(node.children):
            slot = len(names)
            names.append(child.name)
            cols = (np.asarray(node.child_start[ci]),
                    np.asarray(node.child_w[ci]),
                    np.asarray(child.cumw_excl),
                    np.asarray(child.perm))
            offs = []
            for c in cols:
                offs.append(off)
                off += c.shape[0]
            pieces.extend(cols)
            edges.append(ArenaEdge(parent_slot, slot, offs[0], offs[1],
                                   offs[2], offs[3], child.num_rows))
            walk(child, slot)

    walk(root, 0)
    for p in pieces:
        if p.size and int(p.max()) > _I32_MAX:
            return None  # narrowing rule: values must fit int32
    layout = ArenaLayout(tuple(names), root.num_rows,
                         pieces[0].shape[0], tuple(edges), off)
    return pieces, layout


def pack_arena(root: "ShredNode",
               root_prefE: jnp.ndarray) -> Optional["PackedShred"]:
    """Pack a shred's probe tables into a monolithic ``PackedShred`` arena,
    or return ``None`` when the fused path cannot apply (``_arena_pieces``
    narrowing refusals, or a total size over the default VMEM table budget
    — an over-budget monolith would be rejected by every consumer, so the
    int32 copy would only waste device memory). Kept as the monolith-only
    back-compat entry point; index builds go through ``pack_index``, which
    adds the paged alternative."""
    got = _arena_pieces(root, root_prefE)
    if got is None:
        return None
    pieces, layout = got
    if layout.size > _kops.VMEM_PREF_LIMIT:
        return None
    arena = jnp.asarray(
        np.concatenate([p.astype(np.int32) for p in pieces]))
    return PackedShred(arena, layout)


def pack_index(root: "ShredNode", root_prefE: jnp.ndarray, policy=None
               ) -> Tuple[Optional["PackedShred"], Optional["PagedArena"]]:
    """Pack a shred's probe tables for the fused GET/draw kernels, choosing
    the representation by size against the active ``KernelPolicy``
    (DESIGN.md §15). Returns ``(packed, paged)``, at most one non-None:

      * arena fits ``vmem_limit``                     -> monolithic
        ``PackedShred`` (the fused one-launch rung);
      * over the budget, but every page fits it and the total is within
        ``config.PAGED_PACK_LIMIT``                   -> ``PagedArena``
        (the paged streaming rung);
      * narrowing refused, or too large even to page  -> ``(None, None)``
        (the int64 per-node path stands, DESIGN.md §9).

    Mutually exclusive by construction — the engine never pays 2x device
    memory for the same int32 index, and a monolithic arena can still be
    page-sliced at call time (``PagedArena.from_packed``) when a scoped
    policy shrinks the budget under it.
    """
    with obs.span("shred_pack"):
        return _pack_index(root, root_prefE, policy)


def _pack_index(root, root_prefE, policy):
    from repro import config  # local: keep shred importable sans config cycle

    pol = config.current_policy(policy)
    got = _arena_pieces(root, root_prefE)
    if got is None:
        return None, None
    pieces, layout = got
    if layout.size <= pol.vmem_limit:
        arena = jnp.asarray(
            np.concatenate([p.astype(np.int32) for p in pieces]))
        return PackedShred(arena, layout), None
    if (layout.size <= config.PAGED_PACK_LIMIT
            and layout.max_page <= pol.vmem_limit):
        bounds = layout.page_bounds()
        flat = np.concatenate([p.astype(np.int32) for p in pieces])
        pages = tuple(jnp.asarray(flat[s:e]) for s, e in bounds)
        return None, PagedArena(pages, layout)
    return None, None


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Shred:
    """The full shredded random-access index: root node + root prefix vector.

    root_prefE: (n_root + 1,) int64 exclusive prefix of root weights;
    root_prefE[-1] == |mu*(N)| == |Q(db)|.
    packed: the optional fused-GET int32 arena (``pack_index``); ``None``
    when narrowing does not apply or the arena is paged instead — its
    presence is *static* (part of the pytree structure), so jitted
    executors dispatch on it at trace time.
    paged: the page-sliced arena (``PagedArena``) when the index exceeds
    the VMEM budget but pages fit it (DESIGN.md §15); mutually exclusive
    with ``packed``, equally static.
    """

    root: ShredNode
    root_prefE: jnp.ndarray
    rep: str  # 'csr' | 'usr' | 'both' (static)
    packed: Optional[PackedShred] = None
    paged: Optional[PagedArena] = None

    def tree_flatten(self):
        return ((self.root, self.root_prefE, self.packed, self.paged),
                (self.rep,))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(leaves[0], leaves[1], aux[0], leaves[2], leaves[3])

    @property
    def join_size(self) -> jnp.ndarray:
        """|Q(db)| — the full join cardinality, O(1) from the index."""
        return self.root_prefE[-1]


def build_plan(query: JoinQuery) -> JoinTreeNode:
    """Join tree for the query, rerooted so prob_var is flat at the root
    (Proposition 3.1)."""
    tree = gyo_join_tree(query)
    if query.prob_var is not None:
        tree = reroot_for(tree, query.prob_var)
    return tree


def _edge_join_vars(parent_vars: Sequence[str],
                    child_vars: Sequence[str]) -> List[str]:
    """The join attributes of one tree edge, in the canonical (sorted)
    order the grouping keys are built from."""
    return sorted(set(parent_vars) & set(child_vars))


def _edge_keys(parent_rel: Relation, parent_vars: Tuple[str, ...],
               child: ShredNode) -> Tuple[np.ndarray, np.ndarray]:
    """Pass 1 — edge keys: one dense int64 join key per parent / child row.

    A keyless edge (disjoint atoms, i.e. a cross product) maps every row to
    the single key 0: one all-encompassing group, which the downstream
    passes and both GETs handle uniformly (see jointree._gyo_parents)."""
    join_vars = _edge_join_vars(parent_vars, child.variables)
    if join_vars:
        return _dense_gids_np(
            [_np_i64(parent_rel.column(v)) for v in join_vars],
            [_np_i64(child.data.column(v)) for v in join_vars],
        )
    return (np.zeros((parent_rel.num_rows,), np.int64),
            np.zeros((child.num_rows,), np.int64))


def _sorted_group(kc: np.ndarray, weight: np.ndarray):
    """Pass 2 — sorted grouping: stable-sort the child by join key and
    prefix-sum its weights. ``order`` is sorted position -> row id; ties
    keep physical row order (the canonical flatten order depends on it)."""
    order = np.argsort(kc, kind="stable").astype(np.int32)
    kc_sorted = kc[order]
    cumw_excl = np.concatenate(
        [np.zeros((1,), np.int64), np.cumsum(weight[order], dtype=np.int64)])
    return order, kc_sorted, cumw_excl


def _link_columns(kp: np.ndarray, kc_sorted: np.ndarray,
                  order: np.ndarray, cumw_excl: np.ndarray, rep: str):
    """Pass 3 — link columns: each parent row's run boundaries in the sorted
    child (USR) and the chained successor lists (CSR)."""
    n = order.shape[0]
    # Edge keys are dense ranks (``_edge_keys``), so each key's run in the
    # sorted child comes from a histogram: O(n), where a binary search per
    # parent row misses the cache at every step.
    g = int(max(kp.max(initial=0), kc_sorted.max(initial=0))) + 1
    counts = np.bincount(kc_sorted, minlength=g)
    s = (np.cumsum(counts) - counts)[kp]
    e = s + counts[kp]
    child_len = (e - s).astype(np.int32)
    child_w = cumw_excl[e] - cumw_excl[s]
    child_start = s.astype(np.int64)
    # CSR head: first row (in sorted order) of the run; -1 when the run is empty.
    if n == 0:
        child_hd = np.full((kp.shape[0],), -1, np.int32)
    else:
        child_hd = np.where(e > s, order[np.minimum(s, n - 1)],
                            -1).astype(np.int32)

    nxt = None
    if rep in ("csr", "both"):
        # nxt[row] = successor row in the same-key sorted run, else -1.
        same_next = np.concatenate(
            [kc_sorted[1:] == kc_sorted[:-1], np.zeros((1,), np.bool_)])
        succ = np.concatenate([order[1:], np.full((1,), -1, np.int32)])
        nxt = np.zeros((n,), np.int32)
        nxt[order] = np.where(same_next, succ, -1)
    return child_hd, child_start, child_len, child_w, nxt


def _group_child(
    parent_rel: Relation,
    parent_vars: Tuple[str, ...],
    child: ShredNode,
    child_weight: np.ndarray,
    rep: str,
):
    """Group the child by the shared join key; compute the parent's link
    columns. This is the sort-based analogue of CSR-GROUP (paper Fig. 3) and
    of the 2-pass USR grouping, unified (DESIGN.md §3) — now a composition
    of the three reusable passes ``reshred_incremental`` also merges into
    (DESIGN.md §11)."""
    with obs.span("shred_keys"):
        kp, kc = _edge_keys(parent_rel, parent_vars, child)
    with obs.span("shred_sort"):
        order, kc_sorted, cumw_excl = _sorted_group(kc, child_weight)
    with obs.span("shred_link"):
        child_hd, child_start, child_len, child_w, nxt = _link_columns(
            kp, kc_sorted, order, cumw_excl, rep)
    return child_hd, child_start, child_len, child_w, nxt, order, cumw_excl


def _build_node(
    tnode: JoinTreeNode, db: Database, rep: str, owned_above: frozenset
) -> Tuple[ShredNode, np.ndarray]:
    """Build one node and its subtree; returns the node (device arrays)
    and its weight column on the host, which the parent's grouping needs.

    The grouping passes run on the host in numpy: they are exact integer
    sorts and scans, and as eager device ops every index size would be a
    fresh TPU compile of a multi-million-element sort (minutes each). The
    finished columns are placed on the default device."""
    rel = db.instance_for(tnode.atom)
    rel.validate()
    variables = tuple(tnode.atom.variables)
    owned = tuple(v for v in dict.fromkeys(variables) if v not in owned_above)
    below = owned_above | set(variables)

    children = [_build_node(c, db, rep, below) for c in tnode.children]

    n = rel.num_rows
    weight = np.ones((n,), np.int64)
    hds, starts, lens, ws = [], [], [], []
    new_children = []
    for child, child_weight in children:
        hd, st, ln, w, nxt, perm, cume = _group_child(
            rel, variables, child, child_weight, rep)
        with obs.span("shred_place"):
            hds.append(jnp.asarray(hd))
            starts.append(jnp.asarray(st))
            lens.append(jnp.asarray(ln))
            ws.append(jnp.asarray(w))
            new_children.append(dataclasses.replace(
                child, nxt=None if nxt is None else jnp.asarray(nxt),
                perm=jnp.asarray(perm), cumw_excl=jnp.asarray(cume)))
        weight = weight * w  # zero-weight propagation == semijoin reduction

    with obs.span("shred_place"):
        dev_weight = jnp.asarray(weight)
    node = ShredNode(
        name=tnode.atom.name,
        variables=variables,
        owned=owned,
        data=rel.project(tuple(dict.fromkeys(variables))),
        weight=dev_weight,
        children=tuple(new_children),
        child_hd=tuple(hds),
        child_start=tuple(starts),
        child_len=tuple(lens),
        child_w=tuple(ws),
    )
    return node, weight


def root_prefix(weight) -> jnp.ndarray:
    """The root's (R+1,) int64 exclusive weight prefix, summed on the host;
    ``prefix[-1]`` is the join size. Raises beyond ``MAX_JOIN``."""
    w = np.asarray(weight, np.int64)
    total = int(w.sum(dtype=np.uint64)) if w.size else 0
    if total >= MAX_JOIN:
        raise ValueError(f"join size {total} is beyond the supported "
                         f"2^53 (shred.MAX_JOIN)")
    prefix = np.concatenate(
        [np.zeros((1,), np.int64), np.cumsum(w, dtype=np.int64)])
    with obs.span("shred_place"):
        return jnp.asarray(prefix)


def build_shred(db: Database, query: JoinQuery, rep: str = "usr") -> Shred:
    """Construct the random-access index (Proposition 4.4 / 4.5).

    rep='csr'  — chained representation (linked lists; paper's default).
    rep='usr'  — unchained representation (perm + prefix; TPU default).
    rep='both' — build both sets of link columns (shared grouping pass).
    """
    if rep not in ("csr", "usr", "both"):
        raise ValueError(f"rep must be csr|usr|both, got {rep!r}")
    with obs.span("shred_build"):
        plan = build_plan(query)
        root, weight = _build_node(plan, db, rep, frozenset())
        prefE = root_prefix(weight)
        packed, paged = pack_index(root, prefE)
    return Shred(root=root, root_prefE=prefE, rep=rep,
                 packed=packed, paged=paged)


# ---------------------------------------------------------------------------
# Incremental maintenance (DESIGN.md §11)
# ---------------------------------------------------------------------------
#
# ``reshred_incremental`` replays a ``DeltaBatch`` through the three build
# passes without re-sorting the unchanged rows: the delta is sorted on its
# own (O(|delta| log |delta|)) and *merged* into the existing sorted
# grouping; link columns and prefix vectors are re-derived with linear
# scans / binary searches only on the edges whose endpoints changed. The
# merge runs host-side in numpy — it is bulk data movement, not traced
# computation — and its output is bit-identical to a from-scratch
# ``build_shred`` of the post-delta snapshot (property-tested for both
# representations in tests/test_delta.py).

_PACK_LIMIT = 1 << 62  # packed multi-column keys must stay well inside int64


def _np_i64(col) -> np.ndarray:
    """Join-key column as int64, matching dense_keys' cast semantics."""
    return np.asarray(col).astype(np.int64)


def _lex_scalar_keys(sorted_cols: List[np.ndarray],
                     query_cols: List[np.ndarray]):
    """Collapse multi-column keys on both sides into order-isomorphic int64
    scalars. The total order matches ``dense_keys`` (lexsort convention:
    the LAST column is the primary sort key). Returns None when the value
    ranges cannot be packed into an int64 without overflow."""
    if len(sorted_cols) == 1:
        return sorted_cols[0], query_cols[0]
    mins, widths = [], []
    for sc, qc in zip(sorted_cols, query_cols):
        vals = [c for c in (sc, qc) if c.size]
        lo = min(int(c.min()) for c in vals) if vals else 0
        hi = max(int(c.max()) for c in vals) if vals else 0
        mins.append(lo)
        widths.append(hi - lo + 1)
    total = 1
    for w in widths:
        total *= w
        if total >= _PACK_LIMIT:
            return None

    def pack(cols):
        acc = cols[-1] - mins[-1]
        for c, lo, w in zip(cols[-2::-1], mins[-2::-1], widths[-2::-1]):
            acc = acc * w + (c - lo)
        return acc

    return pack(sorted_cols), pack(query_cols)


def _dense_gids_np(sorted_cols: List[np.ndarray],
                   query_cols: List[np.ndarray]):
    """numpy mirror of ``relations.dense_keys``: rank the union of key
    tuples. The host build's edge keys (``_edge_keys``), and the
    incremental merge's fallback for multi-column edges whose raw value
    ranges overflow packing. O((n+m) log (n+m))."""
    n = sorted_cols[0].shape[0]
    cols = [np.concatenate([s, q]) for s, q in zip(sorted_cols, query_cols)]
    order = np.lexsort(tuple(cols))
    diff = np.zeros(order.shape, np.bool_)
    diff[0:1] = True
    for c in cols:
        cs = c[order]
        diff[1:] |= cs[1:] != cs[:-1]
    gid_sorted = np.cumsum(diff.astype(np.int64)) - 1
    gid = np.empty_like(gid_sorted)
    gid[order] = gid_sorted
    return gid[:n], gid[n:]


def _lex_searchsorted(sorted_cols: List[np.ndarray],
                      query_cols: List[np.ndarray], side: str) -> np.ndarray:
    """searchsorted of multi-column keys into a lexicographically sorted
    multi-column sequence (dense_keys total order)."""
    packed = _lex_scalar_keys(sorted_cols, query_cols)
    if packed is None:
        packed = _dense_gids_np(sorted_cols, query_cols)
    return np.searchsorted(packed[0], packed[1], side=side)


def _instance_colmap(atom: Atom, schema: Tuple[str, ...]) -> Dict[str, str]:
    """variable -> physical column, matching Database.instance_for (for a
    variable repeated in the atom, the last occurrence wins)."""
    return {v: c for c, v in zip(schema, atom.variables)}


def _apply_instance_delta(data: Relation, atom: Atom,
                          schema: Tuple[str, ...], rd) -> Relation:
    """The node's post-delta data relation (survivors then inserts), built
    exactly like ``Database.apply`` + ``instance_for`` would (numpy host
    path: one device_put per output column, no eager-op dispatches)."""
    colmap = _instance_colmap(atom, schema)
    keep = ~rd.delete_mask if rd.delete_mask is not None else None
    cols = {}
    for v, col in data.columns.items():
        nv = np.asarray(col)
        if keep is not None:
            nv = nv[keep]
        if rd.inserts:
            ins = np.asarray(rd.inserts[colmap[v]]).astype(nv.dtype)
            nv = np.concatenate([nv, ins])
        cols[v] = place_column(nv)
    return Relation(cols)


def _edge_key_cols(data: Relation, join_vars: List[str],
                   n: int) -> List[np.ndarray]:
    """Row-order int64 key columns of one edge endpoint; a keyless edge
    (cross product) gets the single all-zero pseudo column."""
    if join_vars:
        return [_np_i64(data.column(v)) for v in join_vars]
    return [np.zeros((n,), np.int64)]


@dataclasses.dataclass
class _MergedOrder:
    """One edge's merged sorted grouping, plus the pieces the parent-side
    boundary adjustment reuses (keep mask in old sorted order, the sorted
    insert keys)."""

    perm: np.ndarray                  # (n_new,) int32 sorted pos -> row id
    keys_sorted: List[np.ndarray]     # merged int64 key cols, sorted order
    keep_sorted: np.ndarray           # (n_old,) bool over OLD sorted order
    ins_keys: List[np.ndarray]        # insert key cols, sorted among selves


def _merge_sorted_order(old_child: ShredNode, join_vars: List[str],
                        atom: Atom, schema: Tuple[str, ...],
                        rd) -> _MergedOrder:
    """Merge a child-relation delta into the child's sorted grouping order.

    Survivors keep their relative (already sorted) order; inserts are
    sorted among themselves and merged in, ties resolved survivors-first
    then insert order — exactly the stable argsort of the post-delta rows.
    """
    perm_old = np.asarray(old_child.perm)
    n_old = old_child.num_rows
    keep = (~rd.delete_mask if rd.delete_mask is not None
            else np.ones((n_old,), np.bool_))
    new_id = np.cumsum(keep) - 1                     # old row -> new row id
    keep_sorted = keep[perm_old]
    surv_rows_old = perm_old[keep_sorted]            # sorted order, filtered
    surv_ids = new_id[surv_rows_old] if surv_rows_old.size else surv_rows_old
    n_surv = int(keep.sum())

    kc_old = _edge_key_cols(old_child.data, join_vars, n_old)
    surv_keys = [k[surv_rows_old] for k in kc_old]

    colmap = _instance_colmap(atom, schema)
    d = rd.num_inserts
    if join_vars and rd.inserts:
        ins_raw = [_np_i64(rd.inserts[colmap[v]]) for v in join_vars]
    else:  # keyless edge, or a delete-only delta (d == 0)
        ins_raw = [np.zeros((d,), np.int64)] * max(len(join_vars), 1)
    ins_order = np.lexsort(tuple(ins_raw))           # stable, last col primary
    ins_keys = [k[ins_order] for k in ins_raw]

    # Insertion points: ties place inserts after equal survivors ('right'),
    # matching stable argsort (survivor ids < insert ids).
    ins_pos = _lex_searchsorted(surv_keys, ins_keys, "right")
    fpos_surv = np.arange(n_surv) + np.searchsorted(
        ins_pos, np.arange(n_surv), side="right")
    fpos_ins = ins_pos + np.arange(d)

    perm_new = np.empty((n_surv + d,), np.int32)
    perm_new[fpos_surv] = surv_ids.astype(np.int32)
    perm_new[fpos_ins] = (n_surv + ins_order).astype(np.int32)
    keys_new = []
    for sk, ik in zip(surv_keys, ins_keys):
        col = np.empty((n_surv + d,), np.int64)
        col[fpos_surv] = sk
        col[fpos_ins] = ik
        keys_new.append(col)
    return _MergedOrder(perm_new, keys_new, keep_sorted, ins_keys)


def _np_nxt(keys_sorted: List[np.ndarray], perm: np.ndarray) -> np.ndarray:
    """numpy re-derivation of the CSR chain over merged sorted keys."""
    n = perm.shape[0]
    same_next = np.ones((n,), np.bool_) if n else np.zeros((0,), np.bool_)
    if n:
        same_next[-1] = False
        for k in keys_sorted:
            same_next[:-1] &= k[1:] == k[:-1]
    succ = np.concatenate([perm[1:], np.full((1,), -1, np.int32)])
    nxt_sorted = np.where(same_next, succ, -1).astype(np.int32)
    nxt = np.zeros((n,), np.int32)
    nxt[perm] = nxt_sorted
    return nxt


def _reshred_node(tnode: JoinTreeNode, snode: ShredNode, db: Database,
                  delta, rep: str):
    """Post-order walk mirroring ``_build_node``. Returns
    ``(new_node, rows_changed, weight_changed)``; untouched subtrees are
    returned by reference (``new_node is snode``)."""
    atom = tnode.atom
    rd = delta.relations.get(atom.relation)
    rows_changed = rd is not None

    results = [_reshred_node(tc, sc, db, delta, rep)
               for tc, sc in zip(tnode.children, snode.children)]
    if not rows_changed and all(nc is sc for (nc, _, _), sc
                                in zip(results, snode.children)):
        return snode, False, False

    schema = db.schemas[atom.relation]
    if rows_changed:
        data_new = _apply_instance_delta(snode.data, atom, schema, rd)
    else:
        data_new = snode.data
    m_new = data_new.num_rows

    weight = np.ones((m_new,), np.int64)
    hds, starts, lens, ws, new_children = [], [], [], [], []
    weight_changed = rows_changed
    for i, ((cnode, c_rows, c_weight), c_old) in enumerate(
            zip(results, snode.children)):
        if not rows_changed and not c_rows and not c_weight:
            # Edge untouched: every link column carries over.
            hds.append(snode.child_hd[i])
            starts.append(snode.child_start[i])
            lens.append(snode.child_len[i])
            ws.append(snode.child_w[i])
            new_children.append(cnode)
            weight *= np.asarray(snode.child_w[i])
            continue
        weight_changed = True
        join_vars = _edge_join_vars(snode.variables, cnode.variables)
        tc_atom = tnode.children[i].atom
        merged = None
        if c_rows:
            merged = _merge_sorted_order(
                c_old, join_vars, tc_atom,
                db.schemas[tc_atom.relation], delta.relations[tc_atom.relation])
            perm = merged.perm
        else:
            perm = np.asarray(c_old.perm)
        if c_rows or c_weight:
            w_sorted = np.asarray(cnode.weight)[perm]
            cumw_excl = np.concatenate(
                [np.zeros((1,), np.int64), np.cumsum(w_sorted)])
        else:
            cumw_excl = np.asarray(c_old.cumw_excl)

        # -- run boundaries (s, e) per parent row -----------------------------
        # Delta-proportional re-derivation, never a full child searchsorted:
        # surviving parent rows *adjust* their stored boundaries (subtract
        # the child keys the delta deleted before them, add the ones it
        # inserted — count arithmetic, bit-exact vs searchsorted), and only
        # parent-inserted rows binary-search the child's sorted keys.
        s_old = np.asarray(snode.child_start[i])
        e_old = s_old + np.asarray(snode.child_len[i])
        if not rows_changed and not c_rows:
            # Only subtree weights moved: the sorted order and every run
            # boundary are unchanged; refresh the weight-dependent columns.
            s, e = s_old, e_old
            hd, ln = snode.child_hd[i], snode.child_len[i]
        else:
            kp_cols = _edge_key_cols(data_new, join_vars, m_new)
            d_p = 0
            s_surv, e_surv = s_old, e_old
            if rows_changed:
                rd_p = delta.relations[atom.relation]
                d_p = rd_p.num_inserts
                if rd_p.delete_mask is not None:
                    keep_p = ~rd_p.delete_mask
                    s_surv, e_surv = s_old[keep_p], e_old[keep_p]
            m_surv = m_new - d_p
            kp_surv = [k[:m_surv] for k in kp_cols]  # survivors lead (canon)
            kp_ins = [k[m_surv:] for k in kp_cols]
            keys_sorted = merged.keys_sorted if merged is not None else None
            if c_rows:
                cum_del = np.concatenate(
                    [np.zeros((1,), np.int64),
                     np.cumsum(~merged.keep_sorted)])
                s_surv = (s_surv - cum_del[s_surv]
                          + _lex_searchsorted(merged.ins_keys, kp_surv, "left"))
                e_surv = (e_surv - cum_del[e_surv]
                          + _lex_searchsorted(merged.ins_keys, kp_surv, "right"))
            if d_p:
                if keys_sorted is None:
                    keys_sorted = [k[perm] for k in _edge_key_cols(
                        cnode.data, join_vars, cnode.num_rows)]
                s = np.concatenate(
                    [s_surv, _lex_searchsorted(keys_sorted, kp_ins, "left")])
                e = np.concatenate(
                    [e_surv, _lex_searchsorted(keys_sorted, kp_ins, "right")])
            else:
                s, e = s_surv, e_surv
            n_child = perm.shape[0]
            if n_child == 0:
                hd = np.full((m_new,), -1, np.int32)
            else:
                hd = np.where(e > s, perm[np.minimum(s, n_child - 1)],
                              -1).astype(np.int32)
            ln = (e - s).astype(np.int32)
            hd, ln = jnp.asarray(hd), jnp.asarray(ln)
        w = cumw_excl[e] - cumw_excl[s]
        start = (snode.child_start[i] if s is s_old
                 else jnp.asarray(s.astype(np.int64)))

        if rep in ("csr", "both") and c_rows:
            nxt = jnp.asarray(_np_nxt(merged.keys_sorted, perm))
        else:
            nxt = c_old.nxt
        new_children.append(dataclasses.replace(
            cnode,
            nxt=nxt,
            perm=jnp.asarray(perm) if c_rows else c_old.perm,
            cumw_excl=(jnp.asarray(cumw_excl) if (c_rows or c_weight)
                       else c_old.cumw_excl),
        ))
        hds.append(hd)
        starts.append(start)
        lens.append(ln)
        ws.append(jnp.asarray(w))
        weight *= np.asarray(w)

    new_node = dataclasses.replace(
        snode,
        data=data_new,
        weight=(jnp.asarray(weight) if (weight_changed or rows_changed)
                else snode.weight),
        children=tuple(new_children),
        child_hd=tuple(hds),
        child_start=tuple(starts),
        child_len=tuple(lens),
        child_w=tuple(ws),
    )
    return new_node, rows_changed, weight_changed


def reshred_incremental(base: Shred, db: Database, query: JoinQuery,
                        delta) -> Shred:
    """Merge ``delta`` (a ``core.delta.DeltaBatch``) into an existing index.

    ``base`` must be ``build_shred(db, query, rep=base.rep)`` for the given
    (pre-delta) snapshot ``db``; the result is bit-identical to
    ``build_shred(db.apply(delta), query, rep=base.rep)`` — same arrays,
    same dtypes, same canonical flatten order — at ``O(|delta| log |delta|
    + affected)`` cost instead of a full ``O(N log N)`` rebuild: only the
    delta is sorted, and only edges with a touched endpoint (or a changed
    subtree weight) re-derive their link columns and prefix vectors.

    Untouched relations' nodes are shared with ``base`` by reference.
    Deltas touching relations outside the query return ``base`` unchanged.
    """
    delta = delta.resolved({n: r.num_rows for n, r in db.relations.items()})
    plan = build_plan(query)
    root, rows_changed, weight_changed = _reshred_node(
        plan, base.root, db, delta, base.rep)
    if root is base.root:
        return base
    if rows_changed or weight_changed:
        prefE = root_prefix(root.weight)
    else:
        prefE = base.root_prefE
    # The fused-GET arena is re-packed from the merged arrays (a flat
    # concat — bulk copy, not sort work), keeping it coherent with the
    # incremental index: bit-identical to a from-scratch build's arena,
    # including the packed-vs-paged verdict (pack_index, DESIGN.md §15).
    packed, paged = pack_index(root, prefE)
    return Shred(root=root, root_prefE=prefE, rep=base.rep,
                 packed=packed, paged=paged)
