"""Pallas TPU kernel: carry-chained prefix sum (weights -> pref vector).

Index construction's only non-sort hot loop is the prefix sum over tuple
weights (paper §4: "The prefix vector can clearly be computed in linear
time"). TPU grids execute sequentially per core, so one carry (the running total,
broadcast in a (1, 128) VMEM scratch) threads through the (row-tiled) grid —
one pass, exactly one VMEM read + write per element. Within a tile the scan
is the log-step roll scan of ``kernels/scan.py`` (Mosaic has no
``cumsum``).

Layout: 1-D data is retiled to (rows, 128) by ops.py; each grid step owns a
(block_rows, 128) tile and computes its flat (row-major) running sum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import config

from .scan import carried_cumsum

DEFAULT_BLOCK_ROWS = 64


def _kernel(x_ref, out_ref, carry_ref):
    out_ref[...] = carried_cumsum(x_ref[...], carry_ref)


@config.resolves_interpret
@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def prefix_sum_tiles(x: jnp.ndarray, *, block_rows: int = DEFAULT_BLOCK_ROWS,
                     interpret: bool = None) -> jnp.ndarray:
    """Inclusive prefix sum in flat row-major order over (R, 128) tiles
    (int32 or float32). ``interpret=None`` takes the active policy's mode."""
    assert x.ndim == 2 and x.shape[1] == 128, x.shape
    rows = x.shape[0]
    block_rows = min(block_rows, rows)
    # Traced with x64 off: under jax x64 (which repro.core enables) index
    # maps and loop counters become int64, which Mosaic rejects.
    with jax.enable_x64(False):
        return pl.pallas_call(
            _kernel,
            grid=(pl.cdiv(rows, block_rows),),
            in_specs=[pl.BlockSpec((block_rows, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((block_rows, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            scratch_shapes=[pltpu.VMEM((1, 128), x.dtype)],
            interpret=interpret,
        )(x)
