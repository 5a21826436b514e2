"""Random access into the (virtual) flattened join result (paper §4, Figs 4/5/11/12).

Both GETs are *bulk* by construction: the probe vector ``pos`` is processed
as one data-parallel batch. The paper's sequential "caching optimization"
(resume a linked-list walk / binary search from the previous probe) exists to
amortize work across consecutive probes on a single core; on TPU the same
amortization comes from executing all probes in lockstep vectors, so the bulk
APIs here are the faithful analogue (DESIGN.md §3/§4).

USR-GET: one vectorized binary search per tree node — O(log|db|) depth per
probe, fully parallel across probes. The searches over the *global* exclusive
weight-prefix array are confined to the correct join-key run automatically,
because a run's weight interval [cumw_excl[start], cumw_excl[start+len]) is
contiguous in the global prefix (see shred.py).

Fused USR-GET (rep='usr_fused', DESIGN.md §4 "Fused GET"): the whole
per-node walk collapsed into ONE Pallas kernel launch over the shred's
packed int32 index arena (shred.pack_arena) — root locate + mixed-radix
split + per-child binary search + perm resolution in a single pass, the
arena VMEM-resident across tree levels. Bit-identical to usr_get_rows;
falls back to the per-node path down a static ladder (no arena / arena
over the VMEM budget / Pallas disabled).

CSR-GET: faithful linked-list walk (bounded while_loop), vmapped over probes
— O(log|db| + d) per probe with d the max join degree. Kept as the
paper-faithful baseline; pointer chasing does not vectorize on TPU.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro import config
from repro.kernels import ops
from repro.kernels.fused_draw import fused_draw, fused_draw_ref, fused_sample
from repro.kernels.tree_probe import tree_probe, tree_probe_paged

from .relations import Float64Bits, take_column
from .sampling import PositionSample
from .shred import PagedArena, Shred, ShredNode

__all__ = ["get", "get_rows", "gather_columns", "csr_get_rows",
           "usr_get_rows", "usr_get_rows_fused", "usr_get_rows_paged",
           "csr_get_rows_cached", "fused_available", "paged_available",
           "paged_view", "select_rep", "draw_fused_available",
           "draw_paged_available", "select_draw", "draw_fused",
           "draw_paged"]

I64 = jnp.int64


def _root_locate(shred: Shred, pos: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Binary search the root prefix vector: pos -> (root row j, local offset i).

    When the shred carries a packed arena (static: every prefix value fits
    int32), the search runs through ``ops.searchsorted_prefix`` — the
    Pallas branchless-descent kernel — on int32-narrowed views; the int64
    local offset is still derived from the original prefix, so results are
    bit-identical to the XLA path (DESIGN.md §4).
    """
    prefE = shred.root_prefE
    n = shred.root.num_rows
    # Either index form (monolithic arena or paged) certifies the int32
    # narrowing; the root prefix itself is always within one page.
    if ((shred.packed is not None or shred.paged is not None)
            and n and ops.pallas_preferred()):
        j = jnp.minimum(
            ops.searchsorted_prefix(prefE.astype(jnp.int32),
                                    pos.astype(jnp.int32)),
            n - 1)
    else:
        j = jnp.clip(ops.searchsorted(prefE, pos) - 1, 0,
                     max(n - 1, 0))
    local = pos - prefE[j]
    return j.astype(jnp.int32), local.astype(I64)


def _mixed_radix_split(local: jnp.ndarray, w: jnp.ndarray):
    """``(local % w, local // w)`` for int64 ``local`` and ``w >= 1``, exact
    for |local| < 2^53 (the engine's join-size bound, ``shred.MAX_JOIN``).

    Integer division has no TPU instruction: the compiler expands an int64
    ``//``/``%`` pair into a program that takes it about half a minute to
    build. Here a float64 quotient is only the estimate — the TPU emulates
    float64, so its rounding is not relied on — refined once from the
    (small) integer remainder, then corrected by one either way; every
    step after the estimate is exact integer arithmetic, and the build
    takes under a second.
    """
    wf = w.astype(jnp.float64)
    q = jnp.floor(local.astype(jnp.float64) / wf).astype(I64)
    q = q + jnp.floor((local - q * w).astype(jnp.float64) / wf).astype(I64)
    r = local - q * w
    q = jnp.where(r < 0, q - 1, jnp.where(r >= w, q + 1, q))
    return local - q * w, q


# ---------------------------------------------------------------------------
# USR
# ---------------------------------------------------------------------------

def _usr_child_locate(node: ShredNode, ci: int, rows: jnp.ndarray, idx: jnp.ndarray):
    """Locate offset ``idx`` within the child-ci group of parent ``rows``.

    One global searchsorted over the child's exclusive weight prefix.
    """
    child = node.children[ci]
    start = node.child_start[ci][rows]          # (k,) offsets into sorted order
    cumw_excl = child.cumw_excl                 # (n_c + 1,)
    base = cumw_excl[start]
    target = base + idx
    # smallest jj with cumw_incl[jj] > target  <=>  cumw_excl[jj+1] > target
    jj = jnp.clip(
        ops.searchsorted(cumw_excl, target) - 1,
        0,
        child.num_rows - 1,
    )
    local = target - cumw_excl[jj]
    child_rows = child.perm[jj]
    return child_rows.astype(jnp.int32), local.astype(I64)


def _usr_sub(node: ShredNode, rows: jnp.ndarray, local: jnp.ndarray, out: Dict[str, jnp.ndarray]):
    out[node.name] = rows
    # Mixed-radix split (paper eq. 6-7): child 0 is least significant.
    for ci, child in enumerate(node.children):
        w = node.child_w[ci][rows]
        idx, local = _mixed_radix_split(local, jnp.maximum(w, 1))
        crows, clocal = _usr_child_locate(node, ci, rows, idx)
        _usr_sub(child, crows, clocal, out)


def usr_get_rows(shred: Shred, pos: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """Resolve probe positions to per-node row indices (USR)."""
    assert shred.rep in ("usr", "both"), "index was not built with USR columns"
    rows, local = _root_locate(shred, pos)
    out: Dict[str, jnp.ndarray] = {}
    _usr_sub(shred.root, rows, local, out)
    return out


# ---------------------------------------------------------------------------
# Fused USR (single Pallas pass over the packed arena, DESIGN.md §4)
# ---------------------------------------------------------------------------

def fused_available(shred: Shred, policy=None) -> bool:
    """Static verdict: does this shred take the fused kernel path?
    (arena packed + within the active policy's VMEM budget + kernels not
    disabled). The budget was historically the module constant
    ``FUSED_VMEM_LIMIT``; it now lives on ``config.KernelPolicy`` so tests
    and operators shrink it with ``config.override(...)``."""
    pol = config.current_policy(policy)
    return (shred.packed is not None
            and shred.packed.layout.size <= pol.vmem_limit
            and pol.enabled
            and config.kernel_unsupported("tree_probe", pol) is None)


def paged_view(shred: Shred):
    """The shred's ``PagedArena``, or ``None``: the build-time one when
    ``pack_index`` chose paging, else a page-sliced view of the monolithic
    arena (static slice bounds — a call-time policy with a shrunken
    ``vmem_limit`` pages an already-packed index without a rebuild)."""
    if shred.paged is not None:
        return shred.paged
    if shred.packed is not None:
        return PagedArena.from_packed(shred.packed)
    return None


def paged_available(shred: Shred, policy=None) -> bool:
    """Static verdict for the *paged* rung (DESIGN.md §15): the fused
    monolith does not apply, but an int32 index exists whose every page
    fits the VMEM budget (total within ``config.PAGED_PACK_LIMIT``).
    Sits strictly between ``fused`` and the per-node fallback in the
    ladder — ``fused_available`` wins when both hold."""
    pol = config.current_policy(policy)
    if (not pol.enabled or fused_available(shred, pol)
            or config.kernel_unsupported("tree_probe_paged", pol)):
        return False
    layout = (shred.paged.layout if shred.paged is not None
              else shred.packed.layout if shred.packed is not None else None)
    if layout is None:
        return False
    return (layout.max_page <= pol.vmem_limit
            and layout.size <= config.PAGED_PACK_LIMIT)


def select_rep(shred: Shred, base: str, policy=None) -> str:
    """The executor rep both plan layers share (DESIGN.md §4): given the
    rep a plan would use (``usr``/``csr``), upgrade USR down the kernel
    ladder (``usr_fused``, then ``usr_paged`` when only the pages fit the
    VMEM budget) when the backend prefers Pallas (compiled mode /
    ``REPRO_PALLAS_PREFER=1``). Single source of truth so single-device
    and sharded plans cannot diverge."""
    pol = config.current_policy(policy)
    if base == "usr" and pol.preferred:
        if fused_available(shred, pol):
            return "usr_fused"
        if paged_available(shred, pol):
            return "usr_paged"
    return base


def _require(kernel: str, policy=None) -> None:
    """Raise when an explicitly requested kernel cannot run here (compiled
    mode on a TPU, ``config.TPU_UNLOWERABLE``), naming the compiler's
    reason — an explicit request never silently takes another route."""
    reason = config.kernel_unsupported(kernel, policy)
    if reason:
        raise NotImplementedError(
            f"kernel {kernel!r} does not compile for this backend: {reason}")


def usr_get_rows_fused(shred: Shred, pos: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """Resolve probe positions to per-node row indices in ONE kernel launch.

    Bit-identical to ``usr_get_rows`` (same rows, every node). The fallback
    ladder is static — decided at trace time from the shred's pytree
    structure, never from traced values:

      1. no packed arena (int32 narrowing refused: join > 2^31, or an
         empty node)                      -> per-node USR (or CSR) path;
      2. arena over the VMEM budget       -> the PAGED rung
         (``usr_get_rows_paged``) when every page fits it, else per-node;
      3. ``REPRO_PALLAS_DISABLE=1``       -> per-node path.

    Positions are narrowed to int32 — exact, because a packed arena
    guarantees join_size < 2^31 and callers clamp pads to n - 1 (GET's
    out-of-range lanes are arbitrary-but-masked either way, §4).
    """
    _require("tree_probe")
    if not fused_available(shred):
        if paged_available(shred):
            return usr_get_rows_paged(shred, pos)
        rep = "usr" if shred.rep in ("usr", "both") else "csr"
        return get_rows(shred, pos, rep=rep)
    packed = shred.packed
    k = pos.shape[0]
    tiles = ops.to_tiles(pos.astype(jnp.int32))
    out = tree_probe(packed.arena, tiles, layout=packed.layout,
                     block_rows=ops.tile_for("tree_probe", k),
                     interpret=ops.interpret_default())
    flat = out.reshape(out.shape[0], -1)[:, :k]
    return {name: flat[i] for i, name in enumerate(packed.layout.names)}


def usr_get_rows_paged(shred: Shred, pos: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """The paged rung's GET (DESIGN.md §15): the same walk as the fused
    kernel, streamed page by page through VMEM (``tree_probe_paged`` —
    double-buffered DMA on TPU, per-page launches elsewhere). Bit-identical
    to ``usr_get_rows``/``usr_get_rows_fused`` at every position. Callers
    reach it through ``select_rep``/``get_rows`` (rep ``usr_paged``) or the
    fused ladder's fallback; it assumes ``paged_available`` held at
    selection time."""
    _require("tree_probe_paged")
    pv = paged_view(shred)
    k = pos.shape[0]
    tiles = ops.to_tiles(pos.astype(jnp.int32))
    out = tree_probe_paged(pv.pages, tiles, layout=pv.layout,
                           block_rows=ops.tile_for("tree_probe_paged", k),
                           interpret=ops.interpret_default())
    flat = out.reshape(out.shape[0], -1)[:, :k]
    return {name: flat[i] for i, name in enumerate(pv.layout.names)}


# ---------------------------------------------------------------------------
# Fused one-launch draw (sample + walk in one kernel, DESIGN.md §14)
# ---------------------------------------------------------------------------

def draw_fused_available(shred: Shred, dparams, *, method: str, n: int = 0,
                         policy=None) -> bool:
    """Static *capability* verdict (no preference): can the one-launch
    fused draw — or its pure-jnp reference twin — run this method on this
    shred?  Requires the packed arena within the policy's VMEM budget plus
    the plan-bound parameter vectors (``sampling.fused_draw_params`` —
    ``None`` when int32 narrowing cannot be certified).  ``ptbern_flat``
    additionally materializes n lanes in VMEM, so n shares the budget.
    Deliberately ignores ``policy.enabled``: the reference route runs with
    kernels disabled; ``select_draw`` layers the preference gates on top."""
    pol = config.current_policy(policy)
    if dparams is None or shred.packed is None:
        return False
    if shred.packed.layout.size > pol.vmem_limit:
        return False
    if method == "ptbern_flat":
        return 0 < n <= pol.vmem_limit
    return method == "exprace"


def draw_paged_available(shred: Shred, dparams, *, method: str, n: int = 0,
                         policy=None) -> bool:
    """Static capability verdict for the *paged* draw (DESIGN.md §15): the
    one-launch fused draw cannot apply (arena over the VMEM budget), but
    the sampling half still fits — the root-level parameter vectors ride
    with the root page — and the walk half can stream pages
    (``paged_available``). Same method gates as the fused draw."""
    pol = config.current_policy(policy)
    if dparams is None or not paged_available(shred, pol):
        return False
    if method == "ptbern_flat":
        return 0 < n <= pol.vmem_limit
    return method == "exprace"


def select_draw(shred: Shred, dparams, *, method: str, n: int = 0,
                kernels: str = "auto", policy=None) -> str:
    """Resolve a ``DrawSpec.kernels`` request to the executor draw route —
    ``'fused'`` (one Pallas launch), ``'paged'`` (sample launch + page-
    streamed walk), ``'reference'`` (same math, plain traced jnp) or
    ``'pernode'`` (the F64 multi-launch path).  Decided at plan-bind time,
    like ``select_rep``:

      * ``'auto'``   — fused iff capable AND the policy enables, prefers
                       and hasn't opted out of the fused draw; else paged
                       under the same preference gates when only the pages
                       fit the VMEM budget; else pernode.
      * ``'fused'``  — explicit request: raise unless capable and enabled.
      * ``'paged'``  — explicit request: raise unless the paged rung is
                       capable and enabled (DESIGN.md §15).
      * ``'reference'`` — explicit request: raise unless capable (runs
                       without Pallas — it is the bit-identity oracle).
      * ``'pernode'`` — always honored (the precision arbiter).
    """
    pol = config.current_policy(policy)
    capable = draw_fused_available(shred, dparams, method=method, n=n,
                                   policy=pol)
    paged_capable = draw_paged_available(shred, dparams, method=method, n=n,
                                         policy=pol)
    if kernels == "pernode":
        return "pernode"
    if kernels == "fused":
        _require("fused_draw", pol)
        if not (capable and pol.enabled):
            raise ValueError(
                "kernels='fused' requested but the fused draw is "
                "unavailable here (needs a packed arena within the VMEM "
                "budget, certified int32 narrowing, an exprace/ptbern_flat "
                "method, and kernels enabled)")
        return "fused"
    if kernels == "paged":
        _require("fused_sample", pol)
        _require("tree_probe_paged", pol)
        if not (paged_capable and pol.enabled):
            raise ValueError(
                "kernels='paged' requested but the paged draw is "
                "unavailable here (needs an int32 index whose arena "
                "exceeds the VMEM budget while every page fits it, "
                "certified narrowing, an exprace/ptbern_flat method, and "
                "kernels enabled)")
        return "paged"
    if kernels == "reference":
        if not (capable or paged_capable):
            raise ValueError(
                "kernels='reference' requested but the fused-draw operands "
                "are unavailable here (needs a packed arena within the "
                "VMEM budget and certified int32 narrowing)")
        return "reference"
    if kernels != "auto":
        raise ValueError(f"unknown kernels request {kernels!r}")
    if pol.enabled and pol.fused_draw and pol.preferred:
        if capable and not config.kernel_unsupported("fused_draw", pol):
            return "fused"
        if paged_capable and not config.kernel_unsupported("fused_sample",
                                                           pol):
            return "paged"
    return "pernode"


def draw_fused(shred: Shred, dparams, key, *, method: str, cap: int,
               acap: int = 0, n: int = 0, reference: bool = False,
               policy=None):
    """Run the one-launch draw (kernels/fused_draw.py): key -> per-node
    rows + PositionSample, ONE dispatch.  ``reference=True`` runs the same
    ``draw_core`` + ``tree_walk`` as plain traced jnp instead — bit-
    identical in interpret mode by construction.

    Returns ``(node_rows, ps)``: node name -> (cap,) int32 rows (lanes
    beyond ``ps.count`` arbitrary-but-masked, the GET contract) and a
    ``PositionSample`` with the usual int64/sentinel-n conventions, so
    downstream compaction/masking is route-agnostic."""
    if shred.packed is not None:
        arena, layout = shred.packed.arena, shred.packed.layout
    else:
        # Paged-only index on the reference route: the pages concatenate
        # back to the monolithic arena exactly (contiguous slices).
        arena = jnp.concatenate(shred.paged.pages)
        layout = shred.paged.layout
    key_data = jax.random.key_data(key).astype(jnp.uint32)
    if reference:
        rows, pos, cnt, ovf = fused_draw_ref(
            arena, key_data, dparams, layout=layout,
            method=method, cap=cap, acap=acap, n=n)
    else:
        rows, pos, cnt, ovf = fused_draw(
            arena, key_data, dparams, layout=layout,
            method=method, cap=cap, acap=acap, n=n,
            interpret=ops.interpret_default(policy))
    node_rows = {name: rows[i]
                 for i, name in enumerate(layout.names)}
    ps = PositionSample(pos.astype(I64), cnt.astype(I64), ovf)
    return node_rows, ps


def draw_paged(shred: Shred, dparams, key, *, method: str, cap: int,
               acap: int = 0, n: int = 0, policy=None):
    """The paged rung's draw (DESIGN.md §15): the sampling half in one
    kernel launch (``fused_sample`` — the exact ``draw_core`` the fused
    draw and its reference run, so positions are bit-identical under the
    same key), then the walk half streamed page by page
    (``tree_probe_paged`` — bit-identical to ``tree_walk``). Same return
    contract as ``draw_fused``."""
    pv = paged_view(shred)
    key_data = jax.random.key_data(key).astype(jnp.uint32)
    pos, cnt, ovf = fused_sample(
        key_data, dparams, method=method, cap=cap, acap=acap, n=n,
        interpret=ops.interpret_default(policy))
    # Clamp sentinels for the walk (arbitrary-but-masked, the GET contract).
    wpos = jnp.minimum(pos, dparams["prefE32"][-1] - 1)
    tiles = ops.to_tiles(wpos)
    rows = tree_probe_paged(pv.pages, tiles, layout=pv.layout,
                            block_rows=ops.tile_for("tree_probe_paged", cap),
                            interpret=ops.interpret_default(policy))
    flat = rows.reshape(rows.shape[0], -1)[:, :cap]
    node_rows = {name: flat[i] for i, name in enumerate(pv.layout.names)}
    ps = PositionSample(pos.astype(I64), cnt.astype(I64), ovf)
    return node_rows, ps


# ---------------------------------------------------------------------------
# CSR
# ---------------------------------------------------------------------------

def _csr_walk(child_weight: jnp.ndarray, nxt: jnp.ndarray, hd: jnp.ndarray, idx: jnp.ndarray):
    """Walk the same-key chain until the cumulative weight covers ``idx``.

    Vectorized over probes via vmap; each lane runs its own bounded
    while_loop (paper Fig. 4 lines 11-15, incl. skipping weight-0 tuples).
    """

    def one(h, i):
        def cond(st):
            row, rem = st
            return jnp.logical_and(row >= 0, rem >= child_weight[row])

        def body(st):
            row, rem = st
            return nxt[row], rem - child_weight[row]

        row, rem = jax.lax.while_loop(cond, body, (h, i))
        return row, rem

    return jax.vmap(one)(hd, idx)


def _csr_sub(node: ShredNode, rows: jnp.ndarray, local: jnp.ndarray, out: Dict[str, jnp.ndarray]):
    out[node.name] = rows
    for ci, child in enumerate(node.children):
        w = node.child_w[ci][rows]
        idx, local = _mixed_radix_split(local, jnp.maximum(w, 1))
        hd = node.child_hd[ci][rows]
        crows, clocal = _csr_walk(child.weight, child.nxt, hd, idx)
        crows = jnp.maximum(crows, 0).astype(jnp.int32)  # clamp sentinel lanes
        _csr_sub(child, crows, clocal.astype(I64), out)


def csr_get_rows(shred: Shred, pos: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """Resolve probe positions to per-node row indices (CSR)."""
    assert shred.rep in ("csr", "both"), "index was not built with CSR columns"
    rows, local = _root_locate(shred, pos)
    out: Dict[str, jnp.ndarray] = {}
    _csr_sub(shred.root, rows, local, out)
    return out


# ---------------------------------------------------------------------------
# CSR bulk probe with the paper's caching optimization (Fig. 11)
# ---------------------------------------------------------------------------

def _csr_walk_cached(child_weight, nxt, hd, idx):
    """Faithful Fig.-11 semantics: probes are processed in (ascending-
    position) order and a chain traversal resumes from where the previous
    probe on the SAME list stopped, instead of restarting at the head.

    Realized as one lax.scan over the probe vector carrying
    (prev_head, prev_row, prev_consumed): sequential like the paper's loop —
    this is the *paper-faithful baseline*; the vmapped walk in _csr_walk is
    the data-parallel adaptation benchmarked against it (table6 bench).
    """

    def step(carry, inp):
        prev_head, prev_row, prev_consumed = carry
        h, i = inp
        same = jnp.logical_and(prev_head == h, i >= prev_consumed)
        row0 = jnp.where(same, prev_row, h)
        rem0 = jnp.where(same, i - prev_consumed, i)
        consumed0 = jnp.where(same, prev_consumed, 0)

        def cond(st):
            row, rem, _ = st
            return jnp.logical_and(row >= 0, rem >= child_weight[row])

        def body(st):
            row, rem, cons = st
            w = child_weight[row]
            return nxt[row], rem - w, cons + w

        row, rem, consumed = jax.lax.while_loop(cond, body, (row0, rem0, consumed0))
        return (h, row, consumed), (row, rem)

    init = (jnp.int32(-2), jnp.int32(-1), jnp.zeros((), idx.dtype))
    _, (rows, rems) = jax.lax.scan(step, init, (hd, idx))
    return rows, rems


def csr_get_rows_cached(shred: Shred, pos: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """CSR GET with the caching optimization; expects ascending ``pos``
    (samplers emit sorted positions, the paper's usage)."""
    assert shred.rep in ("csr", "both")
    rows, local = _root_locate(shred, pos)
    out: Dict[str, jnp.ndarray] = {}

    def sub(node: ShredNode, rows, local):
        out[node.name] = rows
        for ci, child in enumerate(node.children):
            w = node.child_w[ci][rows]
            idx, local_next = _mixed_radix_split(local, jnp.maximum(w, 1))
            hd = node.child_hd[ci][rows]
            crows, clocal = _csr_walk_cached(child.weight, child.nxt, hd, idx)
            crows = jnp.maximum(crows, 0).astype(jnp.int32)
            sub(child, crows, clocal.astype(I64))
            local = local_next

    sub(shred.root, rows, local)
    return out


# ---------------------------------------------------------------------------
# public GET
# ---------------------------------------------------------------------------

def get_rows(shred: Shred, pos: jnp.ndarray, rep: str = None) -> Dict[str, jnp.ndarray]:
    rep = rep or ("usr" if shred.rep in ("usr", "both") else "csr")
    with jax.named_scope("usr_locate"):
        if rep == "usr_fused":
            return usr_get_rows_fused(shred, pos)
        if rep == "usr_paged":
            return usr_get_rows_paged(shred, pos)
        if rep == "usr":
            return usr_get_rows(shred, pos)
        return csr_get_rows(shred, pos)


def gather_columns(shred: Shred, node_rows: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    """Per-node row indices -> owned output columns (the gather half of
    GET).  Shared by the positional routes (``get``) and the fused draw,
    whose kernel already resolved the rows in-launch.  A float64 column
    is gathered as its int64 bit patterns (``Float64Bits``), under the
    nested scope ``usr_gather_bits``."""
    out: Dict[str, jnp.ndarray] = {}
    with jax.named_scope("usr_gather"):
        for node in shred.root.nodes():
            rows = node_rows[node.name]
            for v in node.owned:
                col = node.data.column(v)
                if isinstance(col, Float64Bits):
                    with jax.named_scope("usr_gather_bits"):
                        out[v] = take_column(col, rows)
                else:
                    out[v] = jnp.take(col, rows, axis=0)
    return out


def get(shred: Shred, pos: jnp.ndarray, rep: str = None) -> Dict[str, jnp.ndarray]:
    """idx.GET(pos): the bag of join tuples at the given flat positions.

    Returns variable -> (k,) array. Lanes whose pos is out of range
    (>= join_size, the caller's invalid sentinel) contain arbitrary values and
    must be masked by the caller — this keeps GET shape-static.
    """
    return gather_columns(shred, get_rows(shred, pos, rep))
