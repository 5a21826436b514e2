"""Jitted executors behind the engine's entry points (DESIGN.md §7).

One executor = one trace unit. The Poisson-sample executor is the former
``core/poisson.py`` ``_sample_jit`` moved here unchanged, so samples drawn
through the engine are bit-identical to the pre-engine ``PoissonSampler``
under the same PRNG key. ``jax.jit`` caches traces per static
``(cap, rep, n, acap, narrow)`` tuple; the engine's plan cache keeps the jitted
callable (and thus its trace cache) alive across queries with the same
fingerprint, which is what makes warm calls retrace-free.

The batched executor (DESIGN.md §10) is ``jax.vmap`` of the same trace
unit over the PRNG key only — index, weights, and prefix vectors are
broadcast. Because every sampler derives its randomness solely from its
key, lane ``b`` of the batched draw is bit-identical to a single draw
under ``keys[b]`` (asserted in ``tests/test_batched_engine.py``). Batch
size is a *shape*, not a static argument: callers bucket the key vector
to a power of two (``pad_batch_keys``) so warm batches of any size within
a bucket reuse one cached trace.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import probe, sampling
from repro.core.poisson import JoinSample
from repro.core.shred import Shred

__all__ = [
    "sample_executor", "batched_sample_executor", "empty_sample",
    "empty_sample_batch", "uniform_positions_fn", "bucket_size",
    "pad_batch_keys",
]


def _sample_jit(
    shred: Shred, w, p, prefE, key, cap: int, rep: str, method: str, n: int = 0,
    acap: int = 0, project=None, narrow: bool = False,
    route: str = "pernode", dparams=None, massE=None,
) -> JoinSample:
    if route in ("fused", "reference"):
        # One-launch draw (kernels/fused_draw.py, DESIGN.md §14): positions
        # AND per-node rows come out of a single kernel (or its traced-jnp
        # reference twin); only the column gather remains outside.
        node_rows, ps = probe.draw_fused(
            shred, dparams, key, method=method, cap=cap, acap=acap, n=n,
            reference=(route == "reference"))
        cols = probe.gather_columns(shred, node_rows)
    elif route == "paged":
        # Paged rung (DESIGN.md §15): one sampling launch, then the walk
        # streamed page by page — same draw_core stream as the fused route.
        node_rows, ps = probe.draw_paged(
            shred, dparams, key, method=method, cap=cap, acap=acap, n=n)
        cols = probe.gather_columns(shred, node_rows)
    elif method == "exprace":
        ps = sampling.exprace_positions(key, w, p, prefE, massE, cap,
                                        arrival_cap=acap, narrow=narrow)
    elif method == "ptbern_flat":  # n is the static, concrete join size
        ps = sampling.pt_bern_flat_positions(key, p, prefE, n, cap)
    else:
        raise ValueError(f"unknown jit sampling method {method!r}")
    if route not in ("fused", "reference", "paged"):
        pos = jnp.minimum(ps.positions, jnp.maximum(prefE[-1] - 1, 0))  # clamp
        cols = probe.get(shred, pos, rep=rep)
    if project is not None:
        cols = {v: c for v, c in cols.items() if v in project}
    return JoinSample(cols, ps.positions, ps.count, ps.overflow)


def sample_executor(method: str, project: Optional[tuple]):
    """The jitted Poisson-sample executor with (method, project) baked in.

    ``cap``/``rep``/``n``/``acap``/``route`` are static: each distinct
    combination is one cached trace on the returned callable. ``dparams``
    (the plan-bound fused-draw operand vectors) and ``massE`` (the
    plan-bound EXPRACE mass prefix) are pytree operands — ``None`` where
    the route does not use them.
    """
    return jax.jit(
        partial(_sample_jit, method=method, project=project),
        static_argnames=("cap", "rep", "n", "acap", "narrow", "route"),
    )


def _batched_sample_jit(
    shred: Shred, w, p, prefE, keys, cap: int, rep: str, method: str,
    n: int = 0, acap: int = 0, project=None, narrow: bool = False,
    route: str = "pernode", dparams=None, massE=None,
) -> JoinSample:
    one = partial(_sample_jit, shred, w, p, prefE, cap=cap, rep=rep,
                  method=method, n=n, acap=acap, project=project,
                  narrow=narrow, route=route, dparams=dparams, massE=massE)
    return jax.vmap(one)(keys)


def batched_sample_executor(method: str, project: Optional[tuple]):
    """The jitted multi-draw executor: one dispatch serves ``B`` independent
    Poisson draws into ``(B, cap)`` buffers with per-draw counts/overflow.

    Statics are identical to ``sample_executor``; the batch size enters only
    through ``keys.shape[0]``, so each key-bucket size is one cached trace.
    Only ``keys`` is vmapped — the index, parameter vectors, and fused-draw
    operands are closed over and broadcast, so the fused route batches as a
    vmapped single-kernel launch.
    """
    return jax.jit(
        partial(_batched_sample_jit, method=method, project=project),
        static_argnames=("cap", "rep", "n", "acap", "narrow", "route"),
    )


def bucket_size(b: int) -> int:
    """The power-of-two batch bucket ``b`` lands in (DESIGN.md §10)."""
    if b < 1:
        raise ValueError(f"batch size must be >= 1, got {b}")
    return 1 if b <= 1 else 1 << (b - 1).bit_length()


def pad_batch_keys(keys) -> Tuple[jnp.ndarray, int]:
    """Pad a ``(B,)`` key vector to its power-of-two bucket by repeating the
    last key; returns ``(padded_keys, B)``. Padding lanes are discarded by
    the caller after the dispatch — they never reach the result."""
    b = int(keys.shape[0])
    bp = bucket_size(b)
    if bp == b:
        return keys, b
    return keys[jnp.minimum(jnp.arange(bp), b - 1)], b


def empty_sample(shred: Shred, cap: int) -> JoinSample:
    """An all-padding sample (used when |Q(db)| == 0: nothing to probe)."""
    cols = {v: jnp.zeros((cap,), node.data.column(v).dtype)
            for node in shred.root.nodes() for v in node.owned}
    return JoinSample(cols, jnp.zeros((cap,), jnp.int64),
                      jnp.zeros((), jnp.int64), jnp.zeros((), jnp.bool_))


def empty_sample_batch(shred: Shred, cap: int, batch: int) -> JoinSample:
    """The batched all-padding sample: ``empty_sample`` broadcast to B lanes."""
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (batch,) + x.shape),
                        empty_sample(shred, cap))


def uniform_positions_fn(method: str):
    """Position sampler for uniform beta_p (paper §6.1 BERN/GEO/BINOM/HYBRID)."""
    return {
        "bern": sampling.bern_positions,
        "geo": sampling.geo_positions,
        "binom": sampling.binom_positions,
        "hybrid": sampling.hybrid_positions,
    }[method]
