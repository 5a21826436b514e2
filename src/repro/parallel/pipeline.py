"""Pipeline parallelism: GPipe-style microbatch schedule under shard_map.

Layers are split into ``n_stages`` contiguous stages, one per device along a
"stage" mesh axis. Microbatches march through the pipeline with
``collective_permute`` handing activations to the next stage each tick; a
tick runs every stage in parallel on its resident microbatch (SPMD), so a
forward pass takes ``n_micro + n_stages - 1`` ticks with the classic GPipe
bubble fraction (S-1)/(M+S-1).

Scope: forward pipeline (inference / evaluation, or as the building block
for fwd+bwd interleaving). The assigned dry-run cells are covered by
DP×TP×FSDP×SP (DESIGN.md §6); this module is the >2-pod extension path and
is correctness-tested on real multi-device meshes (tests/test_pipeline.py).

Mechanics: every stage holds ONLY its own stage's parameters
(stage-sharded pytree, leading axis = stage). At tick t, stage s computes on
the microbatch that entered the pipe at t-s; a stage is "warming" or
"draining" otherwise — handled by masking (compute runs, results ignored),
the standard SPMD-uniform formulation.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(
    stage_fn: Callable,     # (stage_params, x) -> y, applied by every stage
    stage_params,           # pytree, leaves (n_stages, ...) — stage-sharded
    batch: jnp.ndarray,     # (n_micro, micro, ...) microbatched input
    mesh: Mesh,
    axis: str = "stage",
):
    """Run the GPipe forward schedule. Returns (n_micro, micro, ...) outputs."""
    n_stages = mesh.shape[axis]
    n_micro = batch.shape[0]
    ticks = n_micro + n_stages - 1

    def local(params, batch):
        params = jax.tree.map(lambda x: x[0], params)   # this stage's slice
        s = jax.lax.axis_index(axis)

        feats = batch.shape[2:]
        buf_in = jnp.zeros(batch.shape[1:], batch.dtype)     # resident input
        outs = jnp.zeros_like(batch)                          # stage-0-homed

        def tick(carry, t):
            buf_in, outs = carry
            # stage 0 ingests microbatch t (if any remain)
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            fresh = batch[mb_idx]
            x = jnp.where(s == 0, jnp.where(t < n_micro, fresh, 0 * fresh), buf_in)
            y = stage_fn(params, x)
            # hand activation to the next stage; the last stage's output
            # rings back to stage 0, which records it into ``outs``.
            y_next = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            done_idx = t - (n_stages - 1)
            record = jnp.logical_and(s == 0, done_idx >= 0)
            outs = jnp.where(
                record,
                outs.at[jnp.clip(done_idx, 0, n_micro - 1)].set(y_next),
                outs)
            return (y_next, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf_in, outs), jnp.arange(ticks))
        # broadcast stage 0's recorded outputs to every stage (uniform out)
        outs = jax.lax.psum(jnp.where(s == 0, outs, jnp.zeros_like(outs)), axis)
        return outs[None]  # re-add stage dim for out_specs

    spec_p = jax.tree.map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec_p, P()),          # batch replicated across stages
        out_specs=P(axis),
        check_vma=False,
    )
    return fn(stage_params, batch)[0]


def reference_forward(stage_fn, stage_params, batch):
    """Oracle: apply all stages sequentially (no pipeline)."""
    n_stages = jax.tree.leaves(stage_params)[0].shape[0]

    def apply_all(x):
        for s in range(n_stages):
            p = jax.tree.map(lambda a: a[s], stage_params)
            x = stage_fn(p, x)
        return x

    return jax.vmap(apply_all)(batch)
