"""Int8 gradient compression with error feedback for the DP all-reduce.

At 1000+ nodes the inter-pod (DCN) gradient all-reduce is the scaling wall;
8-bit quantization cuts that traffic 4x vs f32 (2x vs bf16). Per-tensor
symmetric scaling; the quantization residual is carried in an error-feedback
buffer so the *accumulated* update stays unbiased (Seide et al. / EF-SGD) —
plain quantized SGD diverges, EF provably recovers full-precision rates.

Usage inside a shard_map'd train step:
    g_q, scale = compress_int8(g + err)
    g_sum = jax.lax.psum(g_q.astype(jnp.int32), axis)   # int32 ring sum
    g_hat = g_sum.astype(jnp.float32) * scale / n_shards
    err   = (g + err) - decompress_int8(g_q, scale)
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def compress_int8(g: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-tensor int8 quantization. Returns (q, scale)."""
    g32 = g.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(g32)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(g32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


def decompress_int8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def compressed_psum_grads(grads, err, axis: str):
    """EF-compressed gradient psum over a mesh axis (use under shard_map).

    grads/err: pytrees of equal structure. Returns (mean_grads, new_err).
    Scales are psum-maxed so every shard dequantizes consistently.
    """
    n = jax.lax.axis_size(axis)

    def one(g, e):
        corrected = g.astype(jnp.float32) + e
        scale = jnp.maximum(jnp.max(jnp.abs(corrected)), 1e-12) / 127.0
        scale = jax.lax.pmax(scale, axis)  # shared scale across shards
        q = jnp.clip(jnp.round(corrected / scale), -127, 127).astype(jnp.int8)
        new_e = corrected - q.astype(jnp.float32) * scale
        s = jax.lax.psum(q.astype(jnp.int32), axis)
        mean = s.astype(jnp.float32) * scale / n
        return mean.astype(g.dtype), new_e

    flat_g, tree = jax.tree.flatten(grads)
    flat_e = jax.tree.leaves(err)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    return (tree.unflatten([o[0] for o in out]),
            tree.unflatten([o[1] for o in out]))
