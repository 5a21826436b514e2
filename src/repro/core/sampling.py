"""Position sampling (paper §5): BERN / GEO / BINOM / HYBRID and the
non-uniform (Poisson) liftings PTBERN / PTGEO / PTHYBRID, plus EXPRACE —
this repo's beyond-paper, fully-vectorized non-uniform sampler.

Static-shape discipline: every sampler returns a fixed-capacity position
vector plus (count, overflow). Capacity planning lives in estimate.py; on
overflow the caller re-draws with a larger capacity (poisson.py). Positions
use int64 (join sizes reach 1e10 in the paper's EpiQL workload) — core
enables jax x64 on import (see core/__init__.py).

Vmap-safety contract (DESIGN.md §10): ``exprace_positions`` and
``pt_bern_flat_positions`` draw randomness *only* from their PRNG key and
are built entirely from per-lane-deterministic primitives (elementwise
math, sort, cumsum, searchsorted — including the Pallas branchless-descent
searchsorted kernel, which is a fixed unrolled gather sequence and vmaps
by adding a grid dimension — scatter-with-drop) — no host callbacks,
no data-dependent shapes, no cross-lane reductions. ``jax.vmap`` over the
key argument (weights/probabilities/prefixes broadcast) therefore yields,
lane for lane, the *bit-identical* sample a standalone call under that key
produces. The engine's batched multi-draw executor
(``engine/executors.batched_sample_executor``) and the sharded batched
path rely on this; ``tests/test_batched_engine.py`` asserts it for both
methods, both representations, and under a device mesh. Keep new sampler
code inside this envelope (in particular: no ``jax.lax.cond`` whose
branches have key-dependent side conditions on shapes, no host-side
``int(...)``/``float(...)`` of traced values).

EXPRACE (beyond paper, DESIGN.md §3) — exact non-uniform Poisson sampling as
a *thinned Poisson process*, with no sequential per-root loop:

  A Bernoulli(p) trial per unit cell is equivalent to "a Poisson process with
  rate lambda = -ln(1-p) drops >= 1 arrival in the cell" (P[>=1] = 1-e^-lam
  = p; disjoint cells independent). Over all root segments this is ONE
  inhomogeneous Poisson process with piecewise-constant rate, total mass
  Lam = sum_t w_t * lambda_t. Sample it directly:
      M ~ Poisson(Lam); M iid arrival locations via inverse-CDF
      (searchsorted into the cumulative mass); dedupe cells with one sort.
  For p_t > 1/2, sample the *complement* process (failures, rate -ln p_t,
  also <= ln 2 per cell) and invert via the l-th-missing-value formula —
  so the expected arrival count is <= ln2 * E[min(p,1-p) * w] <= 0.70 E[k]
  slots of overhead, for every p in [0, 1] including the exact endpoints.
  All phases are searchsorted / sort / cumsum — O(|N| + C log C) fully
  data-parallel work for capacity C. The paper's PT* methods instead scan
  root tuples sequentially (Fig. 6 loop) — kept below as host oracles.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops

__all__ = [
    "PositionSample",
    "bern_positions",
    "geo_positions",
    "binom_positions",
    "hybrid_positions",
    "exprace_positions",
    "exprace_mass",
    "pt_bern_flat_positions",
    "fused_draw_params",
    "pt_positions_host",
    "HYBRID_THRESHOLD",
]

I64 = jnp.int64
F64 = jnp.float64
HYBRID_THRESHOLD = 0.5  # paper §6.1: GEO wins for p <= 0.5, BERN above
_TINY = 1e-12


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PositionSample:
    """A fixed-capacity probe sequence. positions[i] for i >= count equals the
    sentinel (the join size n) and must be masked downstream."""

    positions: jnp.ndarray  # (cap,) int64, ascending over valid lanes
    count: jnp.ndarray  # () int64 — number of valid positions (<= cap)
    overflow: jnp.ndarray  # () bool — true sample size exceeded cap

    def tree_flatten(self):
        return (self.positions, self.count, self.overflow), None

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)

    @property
    def capacity(self) -> int:
        return self.positions.shape[0]


def _finish(positions, valid, n, more_beyond) -> PositionSample:
    positions = jnp.where(valid, positions, n)
    count = jnp.sum(valid).astype(I64)
    return PositionSample(positions.astype(I64), count, more_beyond)


# ---------------------------------------------------------------------------
# Uniform position sampling over [0, n)
# ---------------------------------------------------------------------------

def bern_positions(key, p, n: int, cap: int) -> PositionSample:
    """Paper's BERN: one Bernoulli(p) trial per position. Theta(n) lanes."""
    u = jax.random.uniform(key, (n,), F64)
    mask = u < p
    total = jnp.sum(mask).astype(I64)
    (idx,) = jnp.nonzero(mask, size=cap, fill_value=n)
    valid = jnp.arange(cap) < jnp.minimum(total, cap)
    return _finish(idx.astype(I64), valid, jnp.asarray(n, I64), total > cap)


def geo_positions(key, p, n, cap: int) -> PositionSample:
    """Paper's GEO (Fig. 6), vectorized: draw ``cap`` geometric gaps at once,
    prefix-sum them into positions. O(cap) regardless of n; exact because a
    Bernoulli(p) process's success indices have iid Geometric(p) gaps."""
    n = jnp.asarray(n, I64)
    p = jnp.asarray(p, F64)
    u = jax.random.uniform(key, (cap,), F64, minval=_TINY)
    safe_p = jnp.clip(p, _TINY, 1.0 - _TINY)
    gaps = jnp.floor(jnp.log(u) / jnp.log1p(-safe_p)).astype(F64)
    gaps = jnp.where(p <= 0.0, jnp.asarray(n, F64) + 1.0, gaps)
    gaps = jnp.where(p >= 1.0, 0.0, gaps)
    gaps = jnp.minimum(gaps, 4.0 * jnp.asarray(n, F64) + 2.0)  # avoid inf->int UB
    positions = ops.blocked_cumsum(gaps.astype(I64)) + jnp.arange(cap, dtype=I64)
    valid = positions < n
    # If the last lane is still in range the process hasn't exhausted [0, n):
    more = jnp.logical_and(cap > 0, valid[-1] if cap > 0 else False)
    return _finish(positions, valid, n, more)


def binom_positions(key, p, n: int, cap: int) -> PositionSample:
    """Paper's BINOM: draw k ~ Binomial(n, p), then a uniform k-subset of
    [0, n). The k-subset is drawn exactly via Gumbel-top-k over the n cells
    (the indices of the k smallest of n iid keys form a uniform k-subset).
    Note: Theta(n log n) here vs the O(n min(p,1-p) + np) of [7]/[23] —
    Vitter-style sequential subset draws don't vectorize; the paper discards
    BINOM after its Fig. 7 anyway (DESIGN.md §9)."""
    kk, ku = jax.random.split(key)
    k = jax.random.binomial(kk, n=jnp.asarray(n, F64), p=jnp.asarray(p, F64)).astype(I64)
    k = jnp.minimum(k, n)
    overflow = k > cap
    k_eff = jnp.minimum(k, cap)
    keys = jax.random.uniform(ku, (n,), F64)
    order = jnp.argsort(keys)  # uniform random permutation
    chosen = jnp.sort(jnp.where(jnp.arange(n) < k_eff, order, n)).astype(I64)
    m = min(n, cap)
    positions = jnp.full((cap,), n, I64).at[:m].set(chosen[:m])
    valid = jnp.arange(cap, dtype=I64) < k_eff
    return _finish(positions, valid, jnp.asarray(n, I64), overflow)


def hybrid_positions(key, p, n: int, cap: int) -> PositionSample:
    """Paper's HYBRID: GEO for p <= 0.5, BERN otherwise (threshold from §6.1)."""
    return jax.lax.cond(
        jnp.asarray(p, F64) <= HYBRID_THRESHOLD,
        lambda k: geo_positions(k, p, n, cap),
        lambda k: bern_positions(k, p, n, cap),
        key,
    )


# ---------------------------------------------------------------------------
# Non-uniform (Poisson) position sampling over root groups
# ---------------------------------------------------------------------------

def _locate_prefix(prefE, q, hi, narrow: bool):
    """clip(searchsorted(prefE, q, 'right') - 1, 0, hi) — routed through the
    Pallas branchless-descent kernel (``ops.searchsorted_prefix``) on
    int32-narrowed views when the caller statically guarantees every value
    fits int32 (``narrow=True``: the compiled plan knows join_size < 2^31
    because the shred packed its fused arena — DESIGN.md §4). Bit-identical
    to the XLA expression either way; float prefixes (EXPRACE's mass
    vector) take ``ops``' XLA fallback — dtypes there never permit."""
    if narrow:
        prefE, q = prefE.astype(jnp.int32), q.astype(jnp.int32)
    return jnp.minimum(ops.searchsorted_prefix(prefE, q), hi).astype(I64)


def _exprace_rates(p, xp=jnp):
    """(complement flags, per-cell Poisson rates) of EXPRACE's thinning, in
    array module ``xp`` (jnp inside the draw, numpy for the host tables)."""
    p = xp.clip(xp.asarray(p, dtype=xp.float64), 0.0, 1.0)
    comp = p > 0.5                      # sample failures instead of successes
    pi = xp.where(comp, 1.0 - p, p)     # process probability, <= 1/2
    lam = -xp.log1p(-xp.minimum(pi, 0.5))  # rate per cell, <= ln 2
    return comp, lam


def _exclusive_cumsum_np(x) -> np.ndarray:
    """Exclusive prefix sum over the last axis (leading zero), numpy."""
    zero = np.zeros(x.shape[:-1] + (1,), x.dtype)
    return np.concatenate([zero, np.cumsum(x, axis=-1)], axis=-1)


def exprace_mass(w, p) -> np.ndarray:
    """EXPRACE's cumulative arrival mass: the (..., R+1) f64 exclusive
    prefix of ``w_t * lam_t`` over the last axis, computed on the host in
    numpy. It depends only on the plan-bound (w, p), so plans compute it
    once per bind and pass it to every draw (``exprace_positions``'
    ``massE``): a float64 prefix sum takes the TPU compiler minutes (it
    emulates f64), and every draw program would otherwise carry one."""
    _, lam = _exprace_rates(np.asarray(p), xp=np)
    return _exclusive_cumsum_np(np.asarray(w, np.float64) * lam)


def exprace_positions(
    key, w, p, prefE, massE, cap: int, arrival_cap: int = 0,
    narrow: bool = False,
) -> PositionSample:
    """EXPRACE: exact non-uniform Poisson sample positions via a thinned
    Poisson process (module docstring). Fully vectorized, exact for all
    p in [0, 1]. Vmap-safe over ``key`` (module docstring contract): the
    engine's batched executor maps this function over split keys.

    w:     (R,) int64   flatten weight of each root tuple (0 = dangling)
    p:     (R,) float   sampling probability of each root tuple (t[y])
    prefE: (R+1,) int64 exclusive prefix of w; prefE[-1] = join size n
    massE: (R+1,) f64   ``exprace_mass(w, p)``, the cumulative arrival mass
    cap:        output position capacity
    arrival_cap: scratch capacity for raw Poisson arrivals (default: cap;
        needs >= ln2/min(p,1-p)-adjusted slack — see estimate.plan_capacity)
    narrow: static caller guarantee that every integer prefix value fits
        int32, enabling the Pallas searchsorted kernel (``_locate_prefix``);
        must not change results (it does not — same clip semantics).
    """
    acap = arrival_cap or cap
    R = w.shape[0]
    n = prefE[-1]
    kM, kV = jax.random.split(key)
    comp, lam = _exprace_rates(p)

    # --- Poisson arrivals over the piecewise-constant-rate line ------------
    Lam = massE[-1]
    M = jax.random.poisson(kM, Lam).astype(I64)
    aM = jnp.minimum(M, acap)
    v = jax.random.uniform(kV, (acap,), F64) * Lam
    avalid = jnp.arange(acap, dtype=I64) < aM
    # Inverse-CDF arrival placement: float mass vector, so the ops wrapper
    # always takes its XLA fallback here (dtypes never permit int32).
    r = _locate_prefix(massE, v, R - 1, False)
    cell = jnp.floor((v - massE[r]) / jnp.maximum(lam[r], _TINY)).astype(I64)
    cell = jnp.clip(cell, 0, jnp.maximum(w[r] - 1, 0))
    gid = jnp.where(avalid, prefE[r] + cell, n)  # global cell id; pads -> n

    # --- dedupe cells (>=1 arrival == one success/failure) -----------------
    gid = jnp.sort(gid, stable=False)  # plain keys: stability changes nothing
    uniq = jnp.logical_and(
        gid < n, jnp.concatenate([jnp.ones((1,), jnp.bool_), gid[1:] != gid[:-1]])
    )
    seg = _locate_prefix(prefE, gid, R - 1, narrow)
    # Per-root count, accumulated in int32 (exact: at most acap arrivals);
    # an int64 scatter-add is several times slower on the TPU.
    hits = jnp.zeros((R,), jnp.int32).at[seg].add(
        uniq.astype(jnp.int32)).astype(I64)
    k_r = jnp.where(comp, w - hits, hits)  # success count per root (exact)
    outE = jnp.concatenate([jnp.zeros((1,), I64), ops.blocked_cumsum(k_r)])
    K = outE[-1]

    # --- compact the unique cells, in (segment, cell) order ----------------
    ucum = ops.blocked_cumsum(uniq.astype(I64))       # inclusive unique count
    urank = ucum - 1                                  # global unique rank
    hitsE = jnp.concatenate([jnp.zeros((1,), I64), ops.blocked_cumsum(hits)])
    local = gid - prefE[seg]                          # cell offset in segment
    BIGPAD = jnp.iinfo(jnp.int64).max
    offE = jnp.concatenate([jnp.zeros((1,), I64), ops.blocked_cumsum(w + 1)])
    lrank = urank - hitsE[seg]                        # rank within segment
    g_val = local - lrank + offE[seg]                 # globally nondecreasing
    # Unique cell i sits where ucum first exceeds i: compaction by search
    # and gather (a scatter of int64 values is slow on the TPU).
    slot = jnp.arange(acap, dtype=I64)
    src = jnp.minimum(ops.searchsorted(ucum, slot), acap - 1)
    live = slot < ucum[-1]
    Fc = jnp.where(live, local[src], BIGPAD)          # compacted cells
    Gc = jnp.where(live, g_val[src], BIGPAD)          # f_i - i + segment offset

    # --- emit output slots --------------------------------------------------
    t = jnp.arange(cap, dtype=I64)
    tvalid = t < jnp.minimum(K, cap)
    rO = _locate_prefix(outE, t, R - 1, narrow)
    l = t - outE[rO]
    # direct: l-th unique arrival of segment rO
    direct_pos = Fc[jnp.clip(hitsE[rO] + l, 0, acap - 1)]
    # complement: l-th missing value among the segment's failures
    q = l + offE[rO]
    c = ops.searchsorted(Gc, q) - hitsE[rO]
    comp_pos = l + jnp.clip(c, 0, jnp.maximum(w[rO] - 1, 0) - l + 1)
    local_out = jnp.where(comp[rO], comp_pos, direct_pos)
    positions = prefE[rO] + jnp.clip(local_out, 0, jnp.maximum(w[rO] - 1, 0))
    overflow = jnp.logical_or(M > acap, K > cap)
    return _finish(positions, tvalid, n, overflow)


def fused_draw_params(w, p, prefE):
    """Plan-bound operand vectors for the one-launch fused draw
    (kernels/fused_draw.py, DESIGN.md §14) — the EXPRACE thinning tables
    (mass prefix, per-cell rates, complement signs) plus the int32-narrowed
    root prefixes, precomputed once per shred bind so the kernel sees only
    VMEM-ready arrays.

    Computed on the host in numpy (engine/plan._bind_shred) and returned as
    numpy arrays. The float tables are accumulated in f64 and cast to f32 —
    the fused route is a float32 sampler end to end (TPU-native; the F64
    multi-launch path stays the precision arbiter, module docstring).
    Returns ``None`` when the int32 narrowing cannot be certified (join + R
    beyond int32, or an empty join) — one more rung of the static fallback
    ladder.
    """
    w = np.asarray(w, np.int64)
    prefE = np.asarray(prefE, np.int64)
    R = int(w.shape[0])
    n = int(prefE[-1])
    # offE[-1] = n + R must fit the int32 complement offsets.
    if n <= 0 or n + R >= (1 << 31) - 1:
        return None
    comp, lam = _exprace_rates(np.asarray(p), xp=np)
    return {
        "massE": exprace_mass(w, p).astype(np.float32),
        "lam": lam.astype(np.float32),
        "sign": np.where(comp, -1, 1).astype(np.int32),
        "w32": w.astype(np.int32),
        "prefE32": prefE.astype(np.int32),
        "cwE": _exclusive_cumsum_np(np.where(comp, w, 0)).astype(np.int32),
        "offE": _exclusive_cumsum_np(w + 1).astype(np.int32),
        "p32": np.clip(np.asarray(p, np.float64), 0.0, 1.0).astype(np.float32),
    }


def pt_bern_flat_positions(key, root_p, prefE, n: int, cap: int) -> PositionSample:
    """Faithful PTBERN, flattened: one Bernoulli trial per flat position with
    that position's root probability. Theta(n) — only for materializable n.
    Vmap-safe over ``key`` (module docstring contract)."""
    flat = jnp.arange(n, dtype=I64)
    r = jnp.clip(jnp.searchsorted(prefE, flat, side="right") - 1, 0, root_p.shape[0] - 1)
    u = jax.random.uniform(key, (n,), F64)
    mask = u < root_p[r]
    total = jnp.sum(mask).astype(I64)
    (idx,) = jnp.nonzero(mask, size=cap, fill_value=n)
    valid = jnp.arange(cap) < jnp.minimum(total, cap)
    return _finish(idx.astype(I64), valid, jnp.asarray(n, I64), total > cap)


# ---------------------------------------------------------------------------
# Paper-faithful sequential host oracles (numpy; used in tests/benchmarks)
# ---------------------------------------------------------------------------

def pt_positions_host(
    rng: np.random.Generator, w: np.ndarray, p: np.ndarray, method: str = "hybrid"
) -> np.ndarray:
    """The paper's PT* loop (§5 "Non-uniform"): iterate root tuples, run the
    uniform sampler per group, shift by the group's base offset. Sequential
    single-core semantics — the reproduction baseline."""
    w = np.asarray(w, np.int64)
    p = np.asarray(p, np.float64)
    base = np.concatenate([[0], np.cumsum(w)])
    out = []
    for t in range(w.shape[0]):
        wt, pt = int(w[t]), float(p[t])
        if wt == 0 or pt <= 0.0:
            continue
        m = method if method != "hybrid" else ("geo" if pt <= HYBRID_THRESHOLD else "bern")
        if m == "bern":
            idx = np.nonzero(rng.random(wt) < pt)[0]
        elif m == "geo":
            idx = []
            i = int(np.floor(np.log(max(rng.random(), _TINY)) / np.log1p(-min(pt, 1 - 1e-15))))
            while i < wt:
                idx.append(i)
                g = int(np.floor(np.log(max(rng.random(), _TINY)) / np.log1p(-min(pt, 1 - 1e-15))))
                i += 1 + g
            idx = np.asarray(idx, np.int64)
        else:
            raise ValueError(m)
        out.append(idx + base[t])
    if not out:
        return np.zeros((0,), np.int64)
    return np.concatenate(out)
