"""Pallas TPU kernel: fused single-pass USR-GET over a packed index arena.

The per-node USR-GET (core/probe.py) issues one XLA ``searchsorted`` plus
separate ``perm``/``child_start``/``child_w`` gathers *per tree node per
probe batch* — ~``3·depth`` HBM-resident ops per GET. This kernel fuses the
whole walk: for one probe tile it performs root-locate plus the full
pre-order tree descent — mixed-radix split (paper eq. 6-7), branchless
power-of-two binary search into each child's exclusive weight prefix, and
``perm`` resolution — in a single ``pallas_call``, reading every per-node
table from ONE flat int32 **index arena** that stays VMEM-resident across
tree levels (DESIGN.md §4 "Fused GET").

The arena is packed at shred-build time (``core.shred.pack_arena``):
``root_prefE`` first, then per tree edge (pre-order) the parent-indexed
``child_start``/``child_w`` columns and the child's ``cumw_excl``/``perm``.
All offsets are static Python ints baked into the kernel via the hashable
``layout`` aux, so the walk unrolls at trace time with zero control flow.

int32-only by design: the arena exists iff every packed value fits int32
(join_size < 2^31 — the common case; larger joins keep the int64 per-node
path per DESIGN.md §9). Probe positions are narrowed to int32 by the
caller, which is exact under the same bound.

**Paged variant** (``tree_probe_paged``, DESIGN.md §15): when the arena
exceeds the VMEM budget but every page (root prefix, then one page per
tree edge — ``core.shred.PagedArena``) fits it, the same walk streams the
pages through VMEM instead of falling back to the per-node path. Two
backend-shaped strategies behind one entry point: on TPU, ONE launch that
double-buffers the pages HBM->VMEM with ``pltpu.make_async_copy`` (copy of
page i+2 overlaps the walk over page i); on GPU/CPU, one small launch per
page with only that page VMEM/shared-resident — no ``pltpu``-only
primitives on that path, so the kernels compile under Pallas's other
lowerings. Both are bit-identical to ``tree_walk`` by construction: the
per-page step is the same arithmetic with page-rebased offsets.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import config

DEFAULT_BLOCK_ROWS = 8  # (8, 128) int32 probe tile


def _descend(arena, off: int, length: int, q):
    """max j in [0, length-1] with arena[off + j] <= q, branchless descent.

    Requires arena[off] == 0 <= q (prefix vectors start at 0), the same
    invariant as ``bsearch_probe``; one VMEM gather per power-of-two step.
    """
    steps = max(1, math.ceil(math.log2(max(length, 2))))
    p = jnp.zeros(q.shape, jnp.int32)
    for k in range(steps - 1, -1, -1):
        cand = p + (1 << k)
        val = jnp.take(arena, off + jnp.minimum(cand, length - 1))
        ok = jnp.logical_and(cand < length, val <= q)
        p = jnp.where(ok, cand, p)
    return p


def tree_walk(arena, pos, layout):
    """The full pre-order USR walk as pure (shape-agnostic) jnp: int32
    probe positions -> per-slot row indices, slot order = ``layout.names``.

    Factored out of the kernel body so the fused one-launch draw
    (kernels/fused_draw.py, DESIGN.md §14) runs the *same* walk on its
    in-kernel sampled positions — one shared implementation is what keeps
    the fused GET and the fused draw's probe phase bit-identical. Only
    elementwise ops and VMEM gathers (``jnp.take``): safe inside Pallas
    kernel bodies (any ``pos`` shape) and in plain traced code.
    """
    # Root locate: pos -> (root row j, local offset) — paper Fig. 4 line 3.
    j = _descend(arena, 0, layout.root_len, pos)
    j = jnp.minimum(j, layout.n_root - 1)
    local = pos - jnp.take(arena, j)
    rows = {0: j}
    locs = {0: local}
    # Pre-order walk, unrolled: edges are emitted in the exact recursion
    # order of probe._usr_sub, so each parent's local offset is peeled in
    # child order (child 0 least significant — paper eq. 6-7).
    for e in layout.edges:
        prow = rows[e.parent]
        w = jnp.take(arena, e.cw_off + prow)
        w_safe = jnp.maximum(w, 1)
        idx = locs[e.parent] % w_safe
        locs[e.parent] = locs[e.parent] // w_safe
        start = jnp.take(arena, e.cs_off + prow)
        target = jnp.take(arena, e.ce_off + start) + idx
        jj = _descend(arena, e.ce_off, e.n_child + 1, target)
        jj = jnp.minimum(jj, e.n_child - 1)
        clocal = target - jnp.take(arena, e.ce_off + jj)
        crow = jnp.take(arena, e.perm_off + jj)
        rows[e.slot] = crow
        locs[e.slot] = clocal
    return [rows[s] for s in range(len(rows))]


def _kernel(arena_ref, q_ref, out_ref, *, layout):
    rows = tree_walk(arena_ref[...], q_ref[...], layout)
    for s, r in enumerate(rows):
        out_ref[s, :, :] = r


@config.resolves_interpret
@functools.partial(jax.jit,
                   static_argnames=("layout", "block_rows", "interpret"))
def tree_probe(
    arena: jnp.ndarray,
    q: jnp.ndarray,
    *,
    layout,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = None,
) -> jnp.ndarray:
    """arena: (layout.size,) int32 packed index; q: (R, 128) int32 probe
    positions. Returns (layout.num_slots, R, 128) int32 — the row index of
    every tree node (slot order = ``layout.names``) for each probe lane.

    The arena is kept wholly VMEM-resident (BlockSpec pinned to block 0);
    callers own the VMEM-budget fallback (core/probe.py, DESIGN.md §9).
    """
    assert q.ndim == 2 and q.shape[1] == 128, q.shape
    rows = q.shape[0]
    grid = (pl.cdiv(rows, block_rows),)
    return pl.pallas_call(
        functools.partial(_kernel, layout=layout),
        grid=grid,
        in_specs=[
            pl.BlockSpec((layout.size,), lambda i: (0,)),      # whole arena
            pl.BlockSpec((block_rows, 128), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((layout.num_slots, block_rows, 128),
                               lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((layout.num_slots,) + q.shape,
                                       jnp.int32),
        interpret=interpret,
    )(arena, q)


# ---------------------------------------------------------------------------
# Paged walk (DESIGN.md §15): stream pages through VMEM instead of pinning
# the whole arena. Bit-identical to tree_walk — same arithmetic, offsets
# rebased to each page's start.
# ---------------------------------------------------------------------------

def _root_page_step(page, pos, *, root_len: int, n_root: int):
    """Root locate against page 0 (== ``tree_walk``'s root phase: the root
    prefix lives at arena offset 0, so the page needs no rebasing)."""
    j = _descend(page, 0, root_len, pos)
    j = jnp.minimum(j, n_root - 1)
    return j, pos - jnp.take(page, j)


def _edge_page_step(page, edge, prow, plocal):
    """One edge of the walk against its own page: identical to the edge
    body of ``tree_walk`` with every arena offset rebased by the page
    start (``edge.cs_off`` — child_start leads the page, so its rebased
    offset is 0). Returns ``(child_row, child_local, parent_local')`` —
    the peeled parent local is threaded back by the caller, mirroring
    ``tree_walk``'s in-place ``locs[e.parent]`` update."""
    base = edge.cs_off
    w = jnp.take(page, (edge.cw_off - base) + prow)
    w_safe = jnp.maximum(w, 1)
    idx = plocal % w_safe
    plocal_new = plocal // w_safe
    start = jnp.take(page, prow)                      # cs rebased to 0
    ce = edge.ce_off - base
    target = jnp.take(page, ce + start) + idx
    jj = _descend(page, ce, edge.n_child + 1, target)
    jj = jnp.minimum(jj, edge.n_child - 1)
    clocal = target - jnp.take(page, ce + jj)
    crow = jnp.take(page, (edge.perm_off - base) + jj)
    return crow, clocal, plocal_new


def _root_page_kernel(page_ref, q_ref, out_ref, *, root_len, n_root):
    j, local = _root_page_step(page_ref[...], q_ref[...],
                               root_len=root_len, n_root=n_root)
    out_ref[0, :, :] = j
    out_ref[1, :, :] = local


def _edge_page_kernel(page_ref, prow_ref, ploc_ref, out_ref, *, edge):
    crow, clocal, pnew = _edge_page_step(page_ref[...], edge,
                                         prow_ref[...], ploc_ref[...])
    out_ref[0, :, :] = crow
    out_ref[1, :, :] = clocal
    out_ref[2, :, :] = pnew


def _paged_launches(pages, q, *, layout, block_rows, interpret):
    """GPU/CPU-shaped paged walk: one small ``pallas_call`` per page, only
    that page resident — portable Pallas (grids + BlockSpecs only, no
    ``pltpu`` primitives). The jitted driver threads parent locals between
    launches (the mixed-radix peel ``tree_walk`` does in-place)."""
    grid = (pl.cdiv(q.shape[0], block_rows),)
    tile = pl.BlockSpec((block_rows, 128), lambda i: (i, 0))

    def stacked(nbuf):
        return (pl.BlockSpec((nbuf, block_rows, 128), lambda i: (0, i, 0)),
                jax.ShapeDtypeStruct((nbuf,) + q.shape, jnp.int32))

    out_spec, out_shape = stacked(2)
    jl = pl.pallas_call(
        functools.partial(_root_page_kernel, root_len=layout.root_len,
                          n_root=layout.n_root),
        grid=grid,
        in_specs=[pl.BlockSpec((layout.root_len,), lambda i: (0,)), tile],
        out_specs=out_spec, out_shape=out_shape,
        interpret=interpret,
    )(pages[0], q)
    rows = {0: jl[0]}
    locs = {0: jl[1]}
    for k, e in enumerate(layout.edges):
        page = pages[k + 1]
        out_spec, out_shape = stacked(3)
        out = pl.pallas_call(
            functools.partial(_edge_page_kernel, edge=e),
            grid=grid,
            in_specs=[pl.BlockSpec((page.shape[0],), lambda i: (0,)),
                      tile, tile],
            out_specs=out_spec, out_shape=out_shape,
            interpret=interpret,
        )(page, rows[e.parent], locs[e.parent])
        rows[e.slot] = out[0]
        locs[e.slot] = out[1]
        locs[e.parent] = out[2]
    return jnp.stack([rows[s] for s in range(layout.num_slots)])


def _dma_paged_kernel(pages_ref, q_ref, out_ref, buf, sem, *, layout):
    """TPU-shaped paged walk: the whole pre-order walk in ONE launch, pages
    double-buffered HBM->VMEM with async copies — the DMA of page i+2
    starts the moment page i's compute frees its buffer slot, so the walk
    over page i+1 overlaps the copy behind it."""
    from jax.experimental.pallas import tpu as pltpu

    npages = len(layout.edges) + 1

    def copy(i, slot):
        return pltpu.make_async_copy(pages_ref.at[i], buf.at[slot],
                                     sem.at[slot])

    copy(0, 0).start()
    if npages > 1:
        copy(1, 1).start()
    pos = q_ref[...]
    copy(0, 0).wait()
    j, local = _root_page_step(buf[0], pos, root_len=layout.root_len,
                               n_root=layout.n_root)
    rows = {0: j}
    locs = {0: local}
    if 2 < npages:
        copy(2, 0).start()              # root page's slot just freed
    for k, e in enumerate(layout.edges):
        i = k + 1
        slot = i % 2
        copy(i, slot).wait()
        crow, clocal, pnew = _edge_page_step(buf[slot], e, rows[e.parent],
                                             locs[e.parent])
        rows[e.slot] = crow
        locs[e.slot] = clocal
        locs[e.parent] = pnew
        if i + 2 < npages:
            copy(i + 2, slot).start()   # page i's slot just freed
    for s in range(layout.num_slots):
        out_ref[s, :, :] = rows[s]


def _paged_dma(pages, q, *, layout, block_rows, interpret):
    from jax.experimental.pallas import tpu as pltpu

    # Pages ride to the kernel stacked+padded in unconstrained (HBM) memory;
    # lane-align the page stride for the DMA engine.
    P = -(-layout.max_page // 128) * 128
    stacked = jnp.stack([jnp.pad(p, (0, P - p.shape[0])) for p in pages])
    grid = (pl.cdiv(q.shape[0], block_rows),)
    return pl.pallas_call(
        functools.partial(_dma_paged_kernel, layout=layout),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec((block_rows, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((layout.num_slots, block_rows, 128),
                               lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((layout.num_slots,) + q.shape,
                                       jnp.int32),
        scratch_shapes=[pltpu.VMEM((2, P), jnp.int32),
                        pltpu.SemaphoreType.DMA((2,))],
        interpret=interpret,
    )(stacked, q)


@functools.partial(jax.jit, static_argnames=("layout", "block_rows",
                                             "interpret", "dma"))
def _tree_probe_paged(pages, q, *, layout, block_rows, interpret, dma):
    assert q.ndim == 2 and q.shape[1] == 128, q.shape
    run = _paged_dma if dma else _paged_launches
    return run(tuple(pages), q, layout=layout, block_rows=block_rows,
               interpret=interpret)


def tree_probe_paged(
    pages,
    q: jnp.ndarray,
    *,
    layout,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = None,
    dma: bool = None,
) -> jnp.ndarray:
    """Paged twin of ``tree_probe``: same contract — ``q`` is (R, 128)
    int32 probe positions, returns (num_slots, R, 128) int32 rows — but the
    index arrives as ``PagedArena.pages`` (per-page slices, layout bounds)
    and only ~one page (plus a double buffer) is VMEM-resident at a time.
    ``dma=None`` picks the strategy from the detected backend
    (``config.backend()``): the in-kernel DMA pipeline on TPU, per-page
    launches elsewhere; tests pin either explicitly. Callers own the
    max-page-vs-budget gate (core/probe.py, DESIGN.md §15)."""
    if dma is None:
        dma = config.backend() == "tpu"
    return _tree_probe_paged(tuple(pages), q, layout=layout,
                             block_rows=block_rows,
                             interpret=config.resolve_interpret(interpret),
                             dma=bool(dma))
