"""Compile-only rehearsals for a TPU v5e: the kernels of the main path at
the widths ``chip_smoke.py`` runs (the JOB star at 2,528,312 titles), the
engine's jitted draw step, and the kernels ``config.TPU_UNLOWERABLE``
declares off the TPU route (each must still fail as declared).

Nothing runs: the TPU compiler builds each program for a described v5e
chip, which finds what interpret mode cannot (unsupported primitives,
unaligned blocks, VMEM overruns). The topology is described inside a
fixture, so every xdist worker collects the same tests and only the worker
running this file loads the TPU library.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro import config

# chip_smoke.py widths: Title rows, and the sample capacity of one draw.
TITLES = 2_528_312
SAMPLE_CAP = 3_381_632


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # Compiles for a described chip cannot be read back from the persistent
    # cache without it; keep the cache out of these tests.
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _tiles(n):
    return -(-n // 128)


def _has_kernel(compiled):
    return "tpu_custom_call" in compiled.as_text()


def test_prefix_sum_compiles(one_chip):
    from repro.kernels import prefix_sum
    for dtype in (jnp.int32, jnp.float32):
        x = _spec((_tiles(TITLES), 128), dtype, one_chip)
        c = prefix_sum.prefix_sum_tiles.lower(
            x, block_rows=prefix_sum.DEFAULT_BLOCK_ROWS,
            interpret=False).compile()
        assert _has_kernel(c)


def test_geo_gaps_compiles(one_chip):
    from repro.kernels import geo_gaps
    u = _spec((_tiles(SAMPLE_CAP), 128), jnp.float32, one_chip)
    p = _spec((), jnp.float32, one_chip)
    c = geo_gaps.geo_gaps_tiles.lower(
        u, p, block_rows=geo_gaps.DEFAULT_BLOCK_ROWS,
        interpret=False).compile()
    assert _has_kernel(c)


def test_bsearch_probe_compiles_at_table_limit(one_chip):
    from repro.kernels import bsearch_probe
    pref = _spec((bsearch_probe.MAX_TABLE,), jnp.int32, one_chip)
    q = _spec((_tiles(SAMPLE_CAP), 128), jnp.int32, one_chip)
    c = bsearch_probe.bsearch_probe.lower(
        pref, q, block_rows=bsearch_probe.DEFAULT_BLOCK_ROWS,
        interpret=False).compile()
    assert _has_kernel(c)


def _small_shred():
    from benchmarks.workloads import job_like
    from repro.core import build_shred
    db, q = job_like(seed=0, scale=200)
    return build_shred(db, q), q


@pytest.mark.parametrize("kernel", sorted(config.TPU_UNLOWERABLE))
def test_declared_unlowerable_kernels_still_fail(one_chip, kernel):
    """Each kernel ``config.TPU_UNLOWERABLE`` takes off the TPU route still
    fails to compile with the declared error — when one starts to
    compile, the declaration (and the route) must change with it."""
    from repro.core import sampling
    from repro.core.shred import PagedArena
    from repro.kernels import fused_draw, tree_probe

    shred, _ = _small_shred()
    sds = lambda x: _spec(x.shape, x.dtype, one_chip)  # noqa: E731
    lay = shred.packed.layout
    qt = _spec((8, 128), jnp.int32, one_chip)
    kd = _spec((2,), jnp.uint32, one_chip)
    dp = jax.tree.map(sds, sampling.fused_draw_params(
        shred.root.weight, shred.root.data.column("p"), shred.root_prefE))
    pv = PagedArena.from_packed(shred.packed)
    lower = {
        "tree_probe": lambda: tree_probe.tree_probe.lower(
            sds(shred.packed.arena), qt, layout=lay, block_rows=8,
            interpret=False),
        "tree_probe_paged": lambda: tree_probe._tree_probe_paged.lower(
            tuple(sds(p) for p in pv.pages), qt, layout=pv.layout,
            block_rows=8, interpret=False, dma=True),
        "fused_draw": lambda: fused_draw.fused_draw.lower(
            sds(shred.packed.arena), kd, dp, layout=lay, method="exprace",
            cap=1024, acap=1024, interpret=False),
        "fused_sample": lambda: fused_draw.fused_sample.lower(
            kd, dp, method="exprace", cap=1024, acap=1024,
            interpret=False),
    }[kernel]
    message, _ = config.TPU_UNLOWERABLE[kernel]
    with pytest.raises(NotImplementedError) as err:
        lower().compile()
    assert message in str(err.value)


def test_engine_draw_step_compiles(one_chip, monkeypatch):
    """The engine's jitted single-draw executor on the route a TPU binds
    (per-node USR GET, XLA EXPRACE, searches as ``ops.kary_searchsorted``
    — what ``ops.searchsorted`` picks on a TPU backend, which this process
    is not), with the plan's own capacities.

    The index shapes come from a real plan, so this runs at scale=2000,
    not at the chip_smoke width: the program is the same at any size (no
    op depends on the index width), and the full-width compile takes the
    TPU compiler over half a minute of host time — ``chip_smoke.py``
    compiles that one on the chip."""
    from benchmarks.workloads import job_like
    from repro.engine import QueryEngine
    from repro.kernels import ops

    db, q = job_like(seed=0, scale=2000)
    plan = QueryEngine(db).compile(q)
    monkeypatch.setattr(ops, "searchsorted", ops.kary_searchsorted)
    sds = lambda x: _spec(x.shape, x.dtype, one_chip)  # noqa: E731
    args = jax.tree.map(sds, (plan.shred, plan.w, plan.p, plan.prefE))
    key = _spec((), jax.random.key(0).dtype, one_chip)
    c = plan._jit.lower(
        *args, key, cap=plan.default_capacity(), rep="usr", n=0,
        acap=plan.arrival_capacity(), narrow=False, route="pernode",
        dparams=None, massE=sds(np.asarray(plan._massE))).compile()
    assert c.memory_analysis().temp_size_in_bytes < 16 << 30
    assert "slice_sizes={1,128}" in c.as_text()  # the searches' row gathers
