"""Pallas TPU kernel: causal flash attention for train/prefill (full
sequence), with GQA head mapping.

The training-side compute hot-spot: at S=32k the score matrix is S² and must
never touch HBM. Tiles: one (block_q, D) query tile is resident per grid
step while (block_k, D) K/V tiles stream through VMEM along the innermost
(sequential) grid axis with the online-softmax (m, l, acc) state in VMEM
scratch — the Pallas twin of models/attention.blockwise_attention (the XLA
path the dry-run lowers), validated against it in interpret mode.

Causality is handled by masking inside the kernel; fully-masked KV tiles
(kv_start > q_end) still occupy grid steps — on real TPU the standard
refinement is a lower-triangular grid via PrefetchScalarGridSpec; kept
simple here and noted (the wasted tiles are ≤ 2x for causal attention).

Grid: (B, H, nQ, nKV); KV innermost so scratch carries per (b, h, q-tile).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import config

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, out_ref, m_ref, l_ref, acc_ref,
            *, scale: float, nk: int, block_q: int, block_k: int,
            causal: bool):
    kv = pl.program_id(3)

    @pl.when(kv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)   # (BQ, D)
    k = k_ref[0, 0].astype(jnp.float32)   # (BK, D)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = kv * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

    m_prev = m_ref[...]                                   # (BQ, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    prob = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(prob, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        prob, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kv == nk - 1)
    def _emit():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        out_ref[0, 0] = out.astype(out_ref.dtype)


@config.resolves_interpret
@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_prefill(
    q: jnp.ndarray,   # (B, H, S, D)
    k: jnp.ndarray,   # (B, KV, S, D)
    v: jnp.ndarray,   # (B, KV, S, D)
    *,
    causal: bool = True,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = None,
) -> jnp.ndarray:
    B, H, S, D = q.shape
    KV = k.shape[1]
    assert H % KV == 0 and S % block_q == 0 and S % block_k == 0
    group = H // KV
    nq, nk = S // block_q, S // block_k
    scale = 1.0 / (D ** 0.5)
    grid = (B, H, nq, nk)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, nk=nk, block_q=block_q,
                          block_k=block_k, causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, kv: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, kv: (b, h // group, kv, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, kv: (b, h // group, kv, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, kv: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
