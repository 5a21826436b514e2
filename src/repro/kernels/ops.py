"""Public jitted wrappers around the Pallas kernels.

Each wrapper owns layout plumbing (1-D <-> (rows, 128) retiling, padding)
and the documented fallbacks:
  * int64 offsets (joins > 2^31) fall back to ``searchsorted`` and a
    blocked XLA scan — TPU has no native 64-bit gathers (DESIGN.md §9);
  * prefix tables above the compare-and-count kernel's ``MAX_TABLE``
    take ``searchsorted`` (a row-gather search tree on a TPU, XLA's
    binary search elsewhere).

Kernel selection is resolved *at call time* from the active
``repro.config.KernelPolicy`` (DESIGN.md §14): every wrapper takes an
``interpret=`` override (tests flip it per-case) and a ``policy=``
override, defaulting to ``config.current_policy()`` — which is the
``override(...)`` context if one is installed, else the policy parsed from
the ``REPRO_PALLAS_*`` environment variables (interpret mode in this CPU
container; compiled mode on real TPUs). A disabled policy
(``KernelPolicy(enabled=False)``, historically ``REPRO_PALLAS_DISABLE=1``)
routes every wrapper through its pure-XLA/jnp fallback (the
searchsorted/cumsum fallbacks for the index kernels, the ``ref`` oracles
for GEO and attention) — the operator escape hatch for a kernel bug,
exercised per-case by the tests (``TestOpsDispatch``).
"""
from __future__ import annotations

import math  # noqa: F401  (re-exported convenience; hoisted per style rule)
from typing import Optional

import jax
import jax.numpy as jnp

from repro import config

from . import ref as _ref
from .autotune import tile_for
from .bsearch_probe import MAX_TABLE as _BSEARCH_MAX_TABLE
from .bsearch_probe import bsearch_probe as _bsearch_tiles
from .geo_gaps import geo_gaps_tiles as _geo_tiles
from .prefix_sum import prefix_sum_tiles as _prefix_tiles
from .flash_decode import flash_decode as _flash_decode
from .flash_prefill import flash_prefill as _flash_prefill

# int32 table entries kept fully VMEM-resident (the fused-GET arena's
# budget — core/probe.py reads the active policy's ``vmem_limit``; this
# constant is the policy default).
VMEM_PREF_LIMIT = config.DEFAULT_VMEM_LIMIT


def interpret_default(policy: Optional[config.KernelPolicy] = None) -> bool:
    """Interpret-mode default, resolved from the active ``KernelPolicy``
    at call time (so tests and CI legs can flip the env var or install an
    ``override(...)`` without re-importing the module)."""
    return config.current_policy(policy).interpret


def pallas_enabled(policy: Optional[config.KernelPolicy] = None) -> bool:
    """False when the active policy disables kernels (historically
    ``REPRO_PALLAS_DISABLE=1``): every wrapper (and the fused dispatches
    in core/probe.py) uses its pure-XLA fallback instead."""
    return config.current_policy(policy).enabled


def pallas_preferred(policy: Optional[config.KernelPolicy] = None) -> bool:
    """Should jitted hot paths *prefer* Pallas kernels over their XLA
    twins when both are available? True in compiled mode (real TPU — the
    kernels are the point); in interpret mode (this CPU container) the
    interpreter's per-access overhead loses to XLA inside an already-jitted
    executor, so hot paths default to XLA unless the policy's ``prefer``
    pins the kernel path (the CI matrix leg sets ``REPRO_PALLAS_PREFER=1``,
    so the interpret-mode kernels are exercised by the whole tier-1 suite,
    not only by the explicit-rep tests). Capability gates
    (``pallas_enabled``, dtype/VMEM fallbacks) still apply on top; explicit
    ``rep='usr_fused'`` / ``kernels='fused'`` requests bypass this
    preference. Resolved at trace time (``KernelPolicy.preferred``)."""
    return config.current_policy(policy).preferred


def _interpret(override: Optional[bool],
               policy: Optional[config.KernelPolicy] = None) -> bool:
    return interpret_default(policy) if override is None else override


def to_tiles(x: jnp.ndarray, fill=0) -> jnp.ndarray:
    """Pad a 1-D vector to a whole number of 128-lanes rows and retile."""
    n = x.shape[0]
    rows = -(-n // 128)
    pad = rows * 128 - n
    return jnp.pad(x, (0, pad), constant_values=fill).reshape(rows, 128)


SEARCH_FAN = 128          # table entries per node of the search tree
SEARCH_CHUNK = 1 << 14    # queries per step (bounds the (chunk, fan) tile)


def kary_searchsorted(table: jnp.ndarray, q: jnp.ndarray,
                      side: str = "right", *, fan: int = SEARCH_FAN,
                      chunk: int = SEARCH_CHUNK) -> jnp.ndarray:
    """``jnp.searchsorted(table, q, side)`` as a ``fan``-ary search tree.

    Level 0 is the table padded and retiled ``(rows, fan)``; level ``i+1``
    holds the first entry of every row of level ``i``, until one row
    remains. A query descends one level per step by fetching the row its
    count so far selects (one row gather) and counting that row's entries
    at or below it (``<`` for ``side='left'``): about log_fan(n) gathers
    of whole rows, against log2(n) scalar gathers for a binary search.
    The result is exact for any sorted table, duplicates included: every
    entry of an earlier row is at most the chosen row's first entry, and
    no entry of a later row qualifies. Queries run ``chunk`` at a time."""
    if side not in ("left", "right"):
        raise ValueError(f"side must be 'left' or 'right', got {side!r}")
    n = table.shape[0]
    dt = jnp.promote_types(table.dtype, q.dtype)
    table, q = table.astype(dt), q.astype(dt)
    big = (jnp.inf if jnp.issubdtype(dt, jnp.floating)
           else jnp.iinfo(dt).max)
    levels, cur = [], table
    while True:
        rows = max(-(-cur.shape[0] // fan), 1)
        lv = jnp.pad(cur, (0, rows * fan - cur.shape[0]),
                     constant_values=big).reshape(rows, fan)
        levels.append(lv)
        if rows == 1:
            break
        cur = lv[:, 0]
    levels.reverse()

    def descend(qc):
        c = jnp.zeros(qc.shape, jnp.int32)
        for i, lv in enumerate(levels):
            row = lv[c]
            hit = row <= qc[:, None] if side == "right" else row < qc[:, None]
            cnt = jnp.sum(hit, axis=1, dtype=jnp.int32)
            c = c * fan + (cnt if i == len(levels) - 1
                           else jnp.maximum(cnt - 1, 0))
        return jnp.minimum(c, n)  # pads (dtype max) count only for q == max

    k = q.shape[0]
    chunk = min(chunk, max(k, 1))
    kp = -(-k // chunk) * chunk
    qp = jnp.pad(q, (0, kp - k)).reshape(-1, chunk)
    return jax.lax.map(descend, qp).reshape(-1)[:k]


def searchsorted(table: jnp.ndarray, q: jnp.ndarray,
                 side: str = "right") -> jnp.ndarray:
    """``jnp.searchsorted(table, q, side)``, int32 result, by the form that
    is fast on the backend: ``kary_searchsorted`` on a TPU, where every
    step of a binary search is a scalar gather, XLA's binary search
    elsewhere."""
    if config.backend() == "tpu":
        return kary_searchsorted(table, q, side)
    return jnp.searchsorted(table, q, side=side)


def searchsorted_prefix(pref: jnp.ndarray, q: jnp.ndarray,
                        *, interpret: Optional[bool] = None,
                        policy: Optional[config.KernelPolicy] = None,
                        ) -> jnp.ndarray:
    """Bulk 'locate offset in prefix vector': max j with pref[j] <= q
    (== ``searchsorted(pref, q, 'right') - 1`` clamped at 0).

    Pallas compare-and-count path for int32 tables of at most
    ``bsearch_probe.MAX_TABLE`` entries (its work is O(table) per query);
    identical XLA binary search for every other dtype (int64 joins > 2^31,
    float mass vectors) or larger table (DESIGN.md §9).
    """
    n = q.shape[0]
    pol = config.current_policy(policy)
    if (pref.dtype != jnp.int32 or q.dtype != jnp.int32
            or pref.shape[0] > min(_BSEARCH_MAX_TABLE, pol.vmem_limit)
            or not pallas_enabled(pol)):
        return jnp.maximum(searchsorted(pref, q) - 1, 0)
    tiles = to_tiles(q)
    out = _bsearch_tiles(pref, tiles,
                         block_rows=tile_for("bsearch_probe", n, pol),
                         interpret=_interpret(interpret, pol))
    return out.reshape(-1)[:n]


def blocked_cumsum(x: jnp.ndarray, block: int = 1024) -> jnp.ndarray:
    """Inclusive prefix sum of a 1-D vector as a two-level XLA scan: scan
    ``block``-wide rows, then add the scanned row totals (recursively).
    Exact for integers — the same values as ``jnp.cumsum`` — and the form
    the TPU compiler handles at index sizes: its ``cumsum`` over one
    multi-million-element window of emulated int64 takes minutes to
    compile, the blocked scan seconds."""
    n = x.shape[0]
    if n <= block:
        return jnp.cumsum(x)
    xp = jnp.pad(x, (0, (-n) % block)).reshape(-1, block)
    inner = jnp.cumsum(xp, axis=1)
    totals = inner[:, -1]
    offsets = blocked_cumsum(totals, block) - totals
    return (inner + offsets[:, None]).reshape(-1)[:n]


def prefix_sum(x: jnp.ndarray, exclusive: bool = False,
               *, interpret: Optional[bool] = None,
               policy: Optional[config.KernelPolicy] = None) -> jnp.ndarray:
    """Prefix sum of a 1-D vector (the index's pref column): the Pallas
    kernel for int32/float32, ``blocked_cumsum`` for every other dtype or
    with kernels disabled."""
    n = x.shape[0]
    pol = config.current_policy(policy)
    if x.dtype not in (jnp.int32, jnp.float32) or not pallas_enabled(pol):
        s = blocked_cumsum(x)
    else:
        s = _prefix_tiles(to_tiles(x),
                          interpret=_interpret(interpret, pol)).reshape(-1)[:n]
    if exclusive:
        s = jnp.concatenate([jnp.zeros((1,), s.dtype), s[:-1]])
    return s


def geo_positions_fused(u: jnp.ndarray, p,
                        *, interpret: Optional[bool] = None,
                        policy: Optional[config.KernelPolicy] = None,
                        ) -> jnp.ndarray:
    """Fused uniform->geometric->positions transform (ascending int32)."""
    n = u.shape[0]
    pol = config.current_policy(policy)
    if not pallas_enabled(pol):
        return _ref.geo_gaps_ref(u.astype(jnp.float32), p)
    tiles = to_tiles(u.astype(jnp.float32), 1.0 - 1e-7)
    return _geo_tiles(tiles, p,
                      interpret=_interpret(interpret, pol)).reshape(-1)[:n]


def decode_attention(q, k, v, bias=None, *, block_s: Optional[int] = None,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """Online-softmax decode attention; pads S up to a block multiple.
    ``block_s=None`` resolves the KV tile through the tuning table
    (``autotune.tile_for``, keyed by sequence length); an explicit value
    pins it."""
    B, H, D = q.shape
    _, KV_H, S, _ = k.shape
    if bias is None:
        bias = jnp.zeros((B, S), jnp.float32)
    if not pallas_enabled():
        return _ref.flash_decode_ref(q, k, v, bias)
    if block_s is None:
        block_s = tile_for("flash_decode", S)
    pad = (-S) % block_s
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        bias = jnp.pad(bias, ((0, 0), (0, pad)), constant_values=-1e30)
    return _flash_decode(q, k, v, bias, block_s=block_s,
                         interpret=_interpret(interpret))


def prefill_attention(q, k, v, *, causal: bool = True,
                      block_q: Optional[int] = None,
                      block_k: Optional[int] = None,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    """Causal flash attention over full sequences (train/prefill); pads S up
    to the block lcm. ``block_q``/``block_k`` default to the tuning-table
    pair (``autotune.tile_for('flash_prefill', S)``); explicit values pin
    either axis independently."""
    B, H, S, D = q.shape
    if not pallas_enabled():
        return _ref.flash_prefill_ref(q, k, v, causal=causal)
    tq, tk = tile_for("flash_prefill", S)
    block_q = tq if block_q is None else block_q
    block_k = tk if block_k is None else block_k
    step = math.lcm(block_q, block_k)
    pad = (-S) % step
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    out = _flash_prefill(q, k, v, causal=causal, block_q=block_q,
                         block_k=block_k, interpret=_interpret(interpret))
    return out[:, :, :S]


# Re-export oracles so tests can write ops.X vs ops.ref.X_ref.
ref = _ref
