"""Pallas TPU kernel: online-softmax decode attention (1 query token vs a
long KV cache), with GQA head mapping.

The serving-side compute hot-spot for the decode_32k / long_500k shapes:
per new token, attention reads the whole KV cache once — purely
memory-bound. The kernel streams K/V in (block_s, head_dim) tiles through
VMEM, maintaining the numerically-stable online softmax (m, l, acc) in VMEM
scratch; nothing of size S is ever materialized. Additive bias (0 / -inf)
carries both padding and windowed-attention masks (zamba2 long-context).

Grid: (batch, q_heads, S_blocks); S is the innermost (sequential) axis so
the (m, l, acc) scratch carries across S tiles of one (b, h) pair.
GQA: q head h reads kv head h // (H // KV_H) via the BlockSpec index_map —
no KV duplication in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import config

DEFAULT_BLOCK_S = 512
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, bias_ref, out_ref, m_ref, l_ref, acc_ref,
            *, scale: float, ns_blocks: int):
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]            # (1, D)
    k = k_ref[0, 0]         # (BS, D)
    v = v_ref[0, 0]         # (BS, D)
    bias = bias_ref[...]    # (1, BS)

    logits = jnp.dot(q.astype(jnp.float32), k.astype(jnp.float32).T,
                     preferred_element_type=jnp.float32) * scale + bias
    m_prev = m_ref[...]                     # (1, 1)
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    probs = jnp.exp(logits - m_new)         # (1, BS)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(probs, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        probs, v.astype(jnp.float32), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(s == ns_blocks - 1)
    def _emit():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        out_ref[0] = out.astype(out_ref.dtype)


@config.resolves_interpret
@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def flash_decode(
    q: jnp.ndarray,      # (B, H, D)
    k: jnp.ndarray,      # (B, KV_H, S, D)
    v: jnp.ndarray,      # (B, KV_H, S, D)
    bias: jnp.ndarray,   # (B, S)  additive, 0 or -inf (padding/window mask)
    *,
    block_s: int = DEFAULT_BLOCK_S,
    interpret: bool = None,
) -> jnp.ndarray:
    B, H, D = q.shape
    _, KV_H, S, _ = k.shape
    assert H % KV_H == 0 and S % block_s == 0, (H, KV_H, S, block_s)
    group = H // KV_H
    ns = S // block_s
    scale = 1.0 / (D ** 0.5)
    grid = (B, H, ns)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, ns_blocks=ns),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, D), lambda b, h, s: (b, h, 0)),
            pl.BlockSpec((1, 1, block_s, D), lambda b, h, s: (b, h // group, s, 0)),
            pl.BlockSpec((1, 1, block_s, D), lambda b, h, s: (b, h // group, s, 0)),
            pl.BlockSpec((1, block_s), lambda b, h, s: (b, s)),
        ],
        out_specs=pl.BlockSpec((1, 1, D), lambda b, h, s: (b, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        scratch_shapes=[
            # (m, l, acc) online-softmax state, carried across the S axis
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, bias)
