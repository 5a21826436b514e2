"""Pallas TPU kernel: bulk "locate offset in a prefix vector" by compare-
and-count — root location (paper Fig. 4 line 3) over small prefix tables.

For a sorted exclusive-prefix array ``pref`` (pref[0] = 0) and a batch of
probe offsets ``q``, computes for each lane the largest j with
pref[j] <= q — i.e. ``searchsorted(pref, q, 'right') - 1`` — as
``#{j : pref[j] <= q} - 1``.

Mosaic lowers no per-lane gather from a 1-D VMEM table, so there is no
binary search here: the table is retiled (T, 128) and kept wholly
VMEM-resident, and each query tile counts the entries at or below every
lane — one table row per loop step, its 128 entries rotated past the
query lanes with ``pltpu.roll``. That is O(table) work per query, which is
why ``ops.searchsorted_prefix`` sends only tables up to ``MAX_TABLE``
entries here and larger ones to XLA's binary search.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import config

DEFAULT_BLOCK_ROWS = 8  # (8, 128) int32 query tile
# Largest prefix table (entries) the compare-and-count kernel serves.
MAX_TABLE = 1 << 12
_PAD = jnp.iinfo(jnp.int32).max


def _kernel(tab_ref, q_ref, out_ref, *, np_len: int):
    q = q_ref[...]

    def row_step(r, cnt):
        row = jnp.broadcast_to(tab_ref[pl.ds(r, 1), :], q.shape)
        for k in range(128):
            rot = row if k == 0 else pltpu.roll(row, k, 1)
            cnt = cnt + (rot <= q).astype(jnp.int32)
        return cnt

    cnt = jax.lax.fori_loop(0, tab_ref.shape[0], row_step,
                            jnp.zeros(q.shape, jnp.int32))
    # Pads (int32 max) count only for q == int32 max; clamp to the table.
    out_ref[...] = jnp.minimum(cnt, np_len) - 1


@config.resolves_interpret
@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def bsearch_probe(pref: jnp.ndarray, q: jnp.ndarray, *,
                  block_rows: int = DEFAULT_BLOCK_ROWS,
                  interpret: bool = None) -> jnp.ndarray:
    """pref: (NP,) int32 sorted with pref[0]==0, NP <= ``MAX_TABLE``;
    q: (R, 128) int32. Returns (R, 128) int32: max j with pref[j] <= q.
    ``interpret=None`` takes the active policy's mode."""
    assert q.ndim == 2 and q.shape[1] == 128, q.shape
    assert pref.shape[0] <= MAX_TABLE, pref.shape
    np_len = pref.shape[0]
    t_rows = -(-np_len // 128)
    tab = jnp.pad(pref, (0, t_rows * 128 - np_len),
                  constant_values=_PAD).reshape(t_rows, 128)
    rows = q.shape[0]
    block_rows = min(block_rows, rows)
    # Traced with x64 off: under jax x64 (which repro.core enables) index
    # maps and loop counters become int64, which Mosaic rejects.
    with jax.enable_x64(False):
        return pl.pallas_call(
            functools.partial(_kernel, np_len=np_len),
            grid=(pl.cdiv(rows, block_rows),),
            in_specs=[
                pl.BlockSpec((t_rows, 128), lambda i: (0, 0)),  # whole table
                pl.BlockSpec((block_rows, 128), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((block_rows, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct(q.shape, jnp.int32),
            interpret=interpret,
        )(tab, q)
