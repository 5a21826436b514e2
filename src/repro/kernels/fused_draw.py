"""ONE Pallas launch from PRNG key to compacted sample rows (DESIGN.md §14).

The multi-launch draw costs ~5+ dispatches per Poisson sample even warm —
key split, arrival generation, prefix searches, per-node GET, compaction —
which is the dispatch floor the B=1/small-batch serving regime pays on
every call. This kernel fuses the whole pipeline: in-kernel Threefry key
folding (kernels/threefry.py), arrival generation, EXPRACE thinning /
PTBERN trials, prefix search over the root prefix, the full pre-order tree
walk against the packed VMEM arena (sharing ``tree_probe.tree_walk`` and
its layout aux), and count/overflow compaction into ``(cap,)`` buffers —
one ``pallas_call``, everything VMEM-resident.

**Bit-identity by construction.** The sampling math lives in pure-jnp
``draw_core``; the kernel body and the multi-launch reference
(``fused_draw_ref`` — plain traced jnp, one XLA dispatch chain) call the
*same* function on the same operands, so in interpret mode they agree bit
for bit (asserted over random acyclic queries by tests/test_fused_draw.py).
The fused stream is **self-defined** (Threefry counters, float32): it does
not reproduce the F64 ``sampling.exprace_positions`` stream — the same
relationship ``kernels/geo_gaps`` has to ``sampling.geo_positions``. The
per-node F64 path remains the precision arbiter; route selection is
static (core/probe.select_draw, engine/plan), with the fallback ladder:
no packed arena / over the VMEM budget / kernels disabled / non-narrowed
shred -> the multi-launch per-node path.

**EXPRACE, sort-free.** The multi-launch EXPRACE draws M ~ Poisson(Lam)
arrival *positions* uniformly and sorts them. In-kernel we instead draw
iid Exp(1) gaps and prefix-sum them: the running sum is a unit-rate
Poisson process on [0, Lam), so arrivals come out *already ascending* and
the scalar Poisson draw, the sort, and every scatter disappear — the
count is just "how many partial sums land below Lam". Cell placement,
dedupe (neighbor compare), per-root success counts, and the l-th-missing-
value complement inversion (p > 1/2) then reduce to branchless binary
searches (``_count_le``) over sorted vectors — gather-only, VMEM-local.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import config

from . import threefry
from .tree_probe import tree_walk

__all__ = ["PARAM_ORDER", "draw_core", "fused_draw", "fused_draw_ref",
           "fused_sample"]

I32 = jnp.int32
F32 = jnp.float32
_TINY = 1e-12  # python float: weak-typed, so it never captures a constant
# Fixed operand order of the plan-bound parameter vectors (see
# sampling.fused_draw_params): a dict in Python, positional in the kernel.
PARAM_ORDER = ("massE", "lam", "sign", "w32", "prefE32", "cwE", "offE", "p32")


def _count_le(vec, q):
    """#elements of the ascending vector ``vec`` that are <= q, branchless
    power-of-two descent (one VMEM gather per step; any ``q`` shape).

    The counting twin of ``tree_probe._descend``: returns values in
    [0, len(vec)], needs no sentinel padding and no arena-style 0-prefix
    invariant, so it searches arbitrary sorted vectors (float mass
    prefixes, running counts, carry-forward complements)."""
    L = vec.shape[0]
    steps = max(1, math.ceil(math.log2(L + 1)))
    p = jnp.zeros(jnp.shape(q), I32)
    for k in range(steps - 1, -1, -1):
        cand = p + (1 << k)
        val = jnp.take(vec, jnp.minimum(cand, L) - 1)
        ok = jnp.logical_and(cand <= L, val <= q)
        p = jnp.where(ok, cand, p)
    return p


def _exprace_core(key, params, acap: int, cap: int):
    """Sorted-gap EXPRACE (module docstring): key -> (positions, count,
    overflow), all int32/f32, no sort, no scatter. Mirrors the semantics
    of ``sampling.exprace_positions`` step for step — per-root success
    counts, complement inversion, clip rules — on the plan-bound
    ``fused_draw_params`` operands."""
    massE, lam, sign = params["massE"], params["lam"], params["sign"]
    w32, prefE32 = params["w32"], params["prefE32"]
    cwE, offE = params["cwE"], params["offE"]
    R = w32.shape[0]
    n32 = prefE32[R]

    # --- arrivals: cumsum of Exp(1) gaps == unit-rate Poisson process ------
    u = threefry.uniforms(key, acap, stream=0)
    v = jnp.cumsum(-jnp.log1p(-u))
    Lam = massE[R]
    avalid = v < Lam
    more_arrivals = avalid[acap - 1]  # scratch exhausted mid-process

    # --- cell placement (inverse CDF into the mass prefix) -----------------
    r = jnp.clip(_count_le(massE, v) - 1, 0, R - 1)
    cell = jnp.floor((v - jnp.take(massE, r))
                     / jnp.maximum(jnp.take(lam, r), _TINY)).astype(I32)
    cell = jnp.clip(cell, 0, jnp.maximum(jnp.take(w32, r) - 1, 0))
    gid = jnp.where(avalid, jnp.take(prefE32, r) + cell, n32)  # ascending

    # --- dedupe (>=1 arrival == one success/failure) -----------------------
    prev = jnp.concatenate([jnp.full((1,), -1, I32), gid[:-1]])
    uniq = jnp.logical_and(gid < n32, gid != prev)
    # Segment from the *root* prefix (not the mass prefix): zero-width
    # roots share a boundary value and must resolve exactly as the
    # reference's searchsorted-right does.
    seg = jnp.clip(_count_le(prefE32, gid) - 1, 0, R - 1)
    U = jnp.cumsum(uniq.astype(I32))                       # incl. unique rank
    S = jnp.cumsum(jnp.where(uniq, jnp.take(sign, seg), 0))

    # --- per-root output prefix, via boundary counts -----------------------
    # B[j] = #arrival lanes with gid < prefE32[j]; then the j-th output
    # boundary is cwE[j] (complement roots emit w - hits) + the signed hit
    # sum up to that lane. hitsE likewise from the unsigned count.
    B = _count_le(gid, prefE32 - 1)
    SB = jnp.where(B > 0, jnp.take(S, jnp.maximum(B - 1, 0)), 0)
    UB = jnp.where(B > 0, jnp.take(U, jnp.maximum(B - 1, 0)), 0)
    outE = cwE + SB                                        # (R+1,) ascending
    hitsE = UB
    K = outE[R]

    # --- complement support: carry-forward g-values ------------------------
    # g = local - rank_within_segment + offE[seg] is ascending over unique
    # lanes; carrying the last unique value over dup/invalid lanes keeps
    # the whole vector sorted so _count_le can binary-search it, and U at
    # the hit lane recovers the unique-entry count the reference gets from
    # its compacted scatter.
    local = gid - jnp.take(prefE32, seg)
    lrank = (U - 1) - jnp.take(hitsE, seg)
    gval = local - lrank + jnp.take(offE, seg)
    gc = jax.lax.cummax(jnp.where(uniq, gval, jnp.full((), -(1 << 30), I32)))

    # --- emit output slots (gather-only compaction) ------------------------
    t = jnp.arange(cap, dtype=I32)
    rO = jnp.clip(_count_le(outE, t) - 1, 0, R - 1)
    l = t - jnp.take(outE, rO)
    wO = jnp.take(w32, rO)
    # direct roots: the l-th unique arrival of segment rO
    i_star = jnp.minimum(_count_le(U, jnp.take(hitsE, rO) + l), acap - 1)
    direct_local = jnp.take(gid, i_star) - jnp.take(prefE32, rO)
    # complement roots: the l-th missing value among the segment's failures
    q = l + jnp.take(offE, rO)
    Lq = _count_le(gc, q)
    c = jnp.where(Lq > 0, jnp.take(U, jnp.maximum(Lq - 1, 0)), 0) \
        - jnp.take(hitsE, rO)
    comp_pos = l + jnp.clip(c, 0, jnp.maximum(wO - 1, 0) - l + 1)
    local_out = jnp.where(jnp.take(sign, rO) < 0, comp_pos, direct_local)
    pos = jnp.take(prefE32, rO) + jnp.clip(local_out, 0,
                                           jnp.maximum(wO - 1, 0))
    count = jnp.minimum(K, cap)
    tvalid = t < count
    positions = jnp.where(tvalid, pos, n32)
    overflow = jnp.logical_or(more_arrivals, K > cap)
    return positions, count, overflow


def _ptbern_core(key, params, n: int, cap: int):
    """Faithful flat PTBERN in one pass: one Bernoulli trial per flat
    position (Theta(n) lanes — the route gate keeps n within the VMEM
    budget), success compaction via a running-count binary search."""
    prefE32, p32 = params["prefE32"], params["p32"]
    R = p32.shape[0]
    n32 = prefE32[R]
    u = threefry.uniforms(key, n, stream=1)
    flat = jnp.arange(n, dtype=I32)
    r = jnp.clip(_count_le(prefE32, flat) - 1, 0, R - 1)
    mask = u < jnp.take(p32, r)
    C = jnp.cumsum(mask.astype(I32))
    total = C[n - 1]
    t = jnp.arange(cap, dtype=I32)
    pos = jnp.minimum(_count_le(C, t), n - 1)  # first lane with C == t+1
    count = jnp.minimum(total, cap)
    positions = jnp.where(t < count, pos, n32)
    return positions, count, total > cap


def draw_core(key, params, *, method: str, cap: int, acap: int, n: int):
    """The shared draw pipeline: sample positions, then walk them. Returns
    ``(positions, count, overflow)`` with the PositionSample conventions
    (positions ascending over valid lanes, sentinel n beyond ``count``).
    Called from the kernel body AND from ``fused_draw_ref`` — sharing this
    function is the bit-identity argument."""
    if method == "exprace":
        return _exprace_core(key, params, acap, cap)
    if method == "ptbern_flat":
        return _ptbern_core(key, params, n, cap)
    raise ValueError(f"unknown fused draw method {method!r}")


def _kernel(arena_ref, key_ref, *rest, layout, method, cap, acap, n):
    param_refs, (rows_ref, pos_ref, cnt_ref, ovf_ref) = rest[:-4], rest[-4:]
    params = {name: ref[...] for name, ref in zip(PARAM_ORDER, param_refs)}
    positions, count, overflow = draw_core(
        key_ref[...], params, method=method, cap=cap, acap=acap, n=n)
    # Clamp sentinels for the walk (GET's out-of-range lanes are
    # arbitrary-but-masked, same contract as the per-node path).
    wpos = jnp.minimum(positions, params["prefE32"][-1] - 1)
    rows = tree_walk(arena_ref[...], wpos, layout)
    for s, r in enumerate(rows):
        rows_ref[s, :] = r
    pos_ref[...] = positions
    cnt_ref[0] = count
    ovf_ref[0] = overflow.astype(I32)


@config.resolves_interpret
@functools.partial(
    jax.jit, static_argnames=("layout", "method", "cap", "acap", "n",
                              "interpret"))
def fused_draw(arena, key_data, params, *, layout, method: str, cap: int,
               acap: int = 0, n: int = 0, interpret: bool = None):
    """The one-launch draw. arena: (layout.size,) int32 packed index;
    key_data: (2,) uint32 (``jax.random.key_data``); params: the
    ``sampling.fused_draw_params`` dict. Returns
    ``(rows (num_slots, cap) i32, positions (cap,) i32, count () i32,
    overflow () bool)`` — rows in ``layout.names`` slot order.

    grid=(1,): every operand is pinned VMEM-resident for the whole draw
    (callers own the VMEM-budget gate — core/probe.py, DESIGN.md §9/§14).
    Vmapping over ``key_data`` batches the launch for the small-bucket
    multi-draw route."""
    operands = [arena, key_data] + [params[k] for k in PARAM_ORDER]
    spec1 = [pl.BlockSpec(x.shape, lambda i, nd=x.ndim: (0,) * nd)
             for x in operands]
    rows, pos, cnt, ovf = pl.pallas_call(
        functools.partial(_kernel, layout=layout, method=method, cap=cap,
                          acap=acap, n=n),
        grid=(1,),
        in_specs=spec1,
        out_specs=[
            pl.BlockSpec((layout.num_slots, cap), lambda i: (0, 0)),
            pl.BlockSpec((cap,), lambda i: (0,)),
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((layout.num_slots, cap), I32),
            jax.ShapeDtypeStruct((cap,), I32),
            jax.ShapeDtypeStruct((1,), I32),
            jax.ShapeDtypeStruct((1,), I32),
        ],
        interpret=interpret,
    )(*operands)
    return rows, pos, cnt[0], ovf[0].astype(jnp.bool_)


def _sample_kernel(key_ref, *rest, method, cap, acap, n):
    param_refs, (pos_ref, cnt_ref, ovf_ref) = rest[:-3], rest[-3:]
    params = {name: ref[...] for name, ref in zip(PARAM_ORDER, param_refs)}
    positions, count, overflow = draw_core(
        key_ref[...], params, method=method, cap=cap, acap=acap, n=n)
    pos_ref[...] = positions
    cnt_ref[0] = count
    ovf_ref[0] = overflow.astype(I32)


@config.resolves_interpret
@functools.partial(
    jax.jit, static_argnames=("method", "cap", "acap", "n", "interpret"))
def fused_sample(key_data, params, *, method: str, cap: int, acap: int = 0,
                 n: int = 0, interpret: bool = None):
    """The sampling HALF of the fused draw as its own one-launch kernel:
    ``draw_core`` without the tree walk — key -> ``(positions (cap,) i32,
    count () i32, overflow () bool)``, PositionSample conventions.

    This is the paged draw route's front end (DESIGN.md §15): when the
    index arena exceeds the VMEM budget the walk must stream pages
    (``tree_probe_paged``) and cannot share the sampler's launch, but the
    sampler itself only touches the root-level parameter vectors — which
    fit VMEM whenever the root page does. Same operands, same Threefry
    streams, so positions are bit-identical to ``fused_draw`` /
    ``fused_draw_ref`` under the same key."""
    operands = [key_data] + [params[k] for k in PARAM_ORDER]
    spec1 = [pl.BlockSpec(x.shape, lambda i, nd=x.ndim: (0,) * nd)
             for x in operands]
    pos, cnt, ovf = pl.pallas_call(
        functools.partial(_sample_kernel, method=method, cap=cap,
                          acap=acap, n=n),
        grid=(1,),
        in_specs=spec1,
        out_specs=[
            pl.BlockSpec((cap,), lambda i: (0,)),
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((cap,), I32),
            jax.ShapeDtypeStruct((1,), I32),
            jax.ShapeDtypeStruct((1,), I32),
        ],
        interpret=interpret,
    )(*operands)
    return pos, cnt[0], ovf[0].astype(jnp.bool_)


@functools.partial(
    jax.jit, static_argnames=("layout", "method", "cap", "acap", "n"))
def fused_draw_ref(arena, key_data, params, *, layout, method: str,
                   cap: int, acap: int = 0, n: int = 0):
    """The multi-launch reference: the *same* ``draw_core`` + ``tree_walk``
    as plain traced jnp (XLA ops, no pallas_call) — the bit-identity
    oracle for the kernel and the ``kernels='reference'`` engine route."""
    positions, count, overflow = draw_core(
        key_data, params, method=method, cap=cap, acap=acap, n=n)
    wpos = jnp.minimum(positions, params["prefE32"][-1] - 1)
    rows = jnp.stack(tree_walk(arena, wpos, layout))
    return rows, positions, count, overflow
