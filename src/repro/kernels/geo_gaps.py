"""Pallas TPU kernel: fused GEO position generation (paper Fig. 6,
vectorized — DESIGN.md §3).

One pass fuses the three stages of the vectorized GEO sampler:
    gap  = floor(ln u / ln(1-p))          (inverse-CDF geometric draw)
    pos  = running_sum(gap + 1) - 1       (carry-chained, like prefix_sum)
so the uniforms tile is read once from VMEM and positions stream out —
instead of three XLA passes (log, floor-div, cumsum) over HBM. The running
sum is ``kernels/scan.py``'s roll scan plus a VMEM carry across tiles.

p arrives as a (1,) SMEM scalar, broadcast to the tile before any math (the
transcendental runs on the vector unit, not the scalar core).
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


def _kernel(p_ref, u_ref, out_ref, carry_ref):
    u = u_ref[...]
    p = jnp.full(u.shape, p_ref[0], jnp.float32)
    # divide (not multiply by reciprocal): floor() amplifies the last-ulp
    # difference into off-by-one positions vs the oracle at small p.
    denom = jnp.log1p(-jnp.clip(p, 1e-12, 1.0 - 1e-7))
    gaps = jnp.floor(jnp.log(jnp.maximum(u, 1e-12)) / denom)
    step = jnp.minimum(gaps, 2_000_000_000.0).astype(jnp.int32) + 1
    out_ref[...] = carried_cumsum(step, carry_ref) - 1


@config.resolves_interpret
@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def geo_gaps_tiles(u: jnp.ndarray, p, *, block_rows: int = DEFAULT_BLOCK_ROWS,
                   interpret: bool = None) -> jnp.ndarray:
    """u: (R, 128) float32 uniforms in (0,1); p: () probability.
    Returns (R, 128) int32 candidate positions (ascending, flat order).
    ``interpret=None`` takes the active policy's mode."""
    assert u.ndim == 2 and u.shape[1] == 128, u.shape
    rows = u.shape[0]
    block_rows = min(block_rows, rows)
    p1 = jnp.asarray(p, jnp.float32).reshape(1)
    # Traced with x64 off: under jax x64 (which repro.core enables) index
    # maps and loop counters become int64, which Mosaic rejects.
    with jax.enable_x64(False):
        return pl.pallas_call(
            _kernel,
            grid=(pl.cdiv(rows, block_rows),),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((block_rows, 128), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((block_rows, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct(u.shape, jnp.int32),
            scratch_shapes=[pltpu.VMEM((1, 128), jnp.int32)],
            interpret=interpret,
        )(p1, u)
