"""In-kernel log-step scans over (rows, 128) tiles, in forms Mosaic lowers.

Mosaic has no ``cumsum`` primitive, so the tile kernels (``prefix_sum``,
``geo_gaps``) scan with ``pltpu.roll``: log2(128) = 7 masked roll+add steps
across lanes, then log2(rows) steps across sublanes on the broadcast row
totals. Integer scans are exact; float scans sum in a tree order (the same
on every backend, interpreted or compiled).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _shift_add(x, axis: int):
    """Inclusive scan of ``x`` along ``axis`` by masked rolls (Hillis-Steele)."""
    n = x.shape[axis]
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    zero = jnp.zeros((), x.dtype)
    s = 1
    while s < n:
        x = x + jnp.where(idx >= s, pltpu.roll(x, s, axis), zero)
        s *= 2
    return x


def tile_cumsum(x):
    """Inclusive prefix sum of a (rows, 128) tile in flat row-major order."""
    lanes = _shift_add(x, 1)
    totals = jnp.broadcast_to(lanes[:, x.shape[1] - 1:], x.shape)
    row_off = _shift_add(totals, 0) - totals       # exclusive over rows
    return lanes + row_off


def carried_cumsum(x, carry_ref):
    """``tile_cumsum(x)`` plus the running total of every earlier tile, held
    broadcast in a (1, 128) VMEM scratch that this call advances.

    The carry stays a vector — no reduction, no scalar store: Mosaic
    lowers ``jnp.sum`` through jnp, which re-traces it with 64-bit
    accumulators whenever jax x64 is on (repro.core turns it on)."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        carry_ref[...] = jnp.zeros(carry_ref.shape, carry_ref.dtype)

    out = tile_cumsum(x) + carry_ref[...]
    rows, lanes = x.shape
    carry_ref[...] = jnp.broadcast_to(out[rows - 1:, lanes - 1:],
                                      carry_ref.shape)
    return out
