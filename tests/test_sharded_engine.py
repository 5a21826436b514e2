"""The sharded engine path (DESIGN.md §8): planner, correctness vs the
single-device engine, and the stacked-shred cache contract.

(a) sharded full-join == single-device full join (bit-identical, order
    included: shard flattens concatenate to the global flatten) and
    sharded samples are valid join tuples, bit-reproducible against a
    host loop folding the shard index into the same base key;
(b) a second call with the same (fingerprint, mesh) never rebuilds the
    stacked shred (CacheStats counters);
(c) the shard planner respects data axes and ``min_shard_rows``.

These tests run on whatever devices exist: the in-process tests force the
stacked path via explicit ``axes`` (so 1-device CI still exercises it),
and the CI 8-virtual-device matrix leg (XLA_FLAGS
--xla_force_host_platform_device_count=8) runs them on a real multi-device
mesh. The slow subprocess test pins 8 devices regardless.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import pytest

from repro.core import Atom, Database, JoinQuery
from repro.core.distributed import (
    build_stacked_shred, partition_root, semijoin_filter,
)
from repro.engine import CapacityPolicy, QueryEngine, ShardedPlan, plan_shards
from repro.engine.executors import _sample_jit


@pytest.fixture(scope="module")
def db():
    rng = np.random.default_rng(11)
    return Database.from_columns({
        "R": {"x": rng.integers(0, 12, 90), "p": rng.random(90) * 0.5},
        "S": {"x": rng.integers(0, 12, 140), "y": rng.integers(0, 9, 140)},
        "T": {"y": rng.integers(0, 9, 60), "z": np.arange(60)},
    })


@pytest.fixture(scope="module")
def query():
    return JoinQuery((Atom.of("R", "x", "p"), Atom.of("S", "x", "y"),
                      Atom.of("T", "y", "z")), prob_var="p")


@pytest.fixture(scope="module")
def mesh():
    return jax.make_mesh((len(jax.devices()),), ("data",))


def _tuples(cols, keys, k=None):
    arrs = [np.asarray(cols[v]) for v in keys]
    if k is not None:
        arrs = [a[:k] for a in arrs]
    return list(zip(*arrs))


# -- (c) shard planner ------------------------------------------------------

def test_plan_shards_picks_data_axes():
    devs = jax.devices()
    mesh = jax.make_mesh((len(devs), 1), ("data", "model"))
    sp = plan_shards(mesh, root_rows=10_000)
    if len(devs) > 1:
        assert sp.axes == ("data",) and sp.num_shards == len(devs)
    else:
        assert sp.axes == () and sp.num_shards == 1
    # model-only meshes never shard the root
    mm = jax.make_mesh((len(devs),), ("model",))
    assert plan_shards(mm, root_rows=10_000).num_shards == 1


def test_plan_shards_min_rows_floor():
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    tight = CapacityPolicy(min_shard_rows=10**9)
    assert plan_shards(mesh, root_rows=100, policy=tight).num_shards == 1
    # explicit axes are honored regardless of the floor
    sp = plan_shards(mesh, root_rows=1, policy=tight, axes=("data",))
    assert sp.num_shards == len(jax.devices())


# -- library layer ----------------------------------------------------------

def test_partition_root_covers_and_pads(db, query):
    part = partition_root(db, query, 4)
    assert sum(part.valid) == 90
    assert all(d.relations[part.root_name].num_rows == part.rows_per_shard
               for d in part.shards)


def test_semijoin_filter_preserves_join(db, query):
    filtered = semijoin_filter(db, query)
    engine = QueryEngine(db)
    a = engine.full_join(query)
    b = QueryEngine(filtered).full_join(query)
    for v in a:
        np.testing.assert_array_equal(np.asarray(a[v]), np.asarray(b[v]))
    # it only ever shrinks the non-root relations
    assert filtered.relations["S"].num_rows <= db.relations["S"].num_rows
    assert filtered.relations["R"].num_rows == db.relations["R"].num_rows


def test_stacked_shred_join_sizes(db, query):
    st = build_stacked_shred(db, query, 4)
    assert st.join_size == QueryEngine(db).join_size(query)


# -- (a) correctness vs the single-device engine ----------------------------

def test_sharded_full_join_bit_identical(db, query, mesh):
    engine = QueryEngine(db)
    got = engine.full_join(query, mesh=mesh, axes=("data",))
    want = engine.full_join(query)
    assert set(got) == set(want)
    for v in want:
        np.testing.assert_array_equal(np.asarray(got[v]), np.asarray(want[v]))


def test_sharded_sample_is_valid_and_fold_reproducible(db, query, mesh):
    engine = QueryEngine(db)
    plan = engine.compile_sharded(query, mesh, axes=("data",))
    assert isinstance(plan, ShardedPlan)
    key = jax.random.key(7)
    smp = engine.sample(query, key, mesh=mesh, axes=("data",))
    k = int(smp.count)
    full = engine.full_join(query)
    keys = tuple(sorted(full))
    fullset = set(_tuples(full, keys))
    got = _tuples(smp.columns, keys, k)
    assert all(t in fullset for t in got)

    # Host emulation of the device-folded key scheme: bit-identical.
    st = plan.stacked
    ref, ref_pos, base = [], [], 0
    for s in range(plan.num_shards):
        shred_s = jax.tree.map(lambda x: x[s], st.shred)
        r = _sample_jit(shred_s, st.w[s], st.p[s], st.prefE[s],
                        jax.random.fold_in(key, s), cap=plan.cap,
                        rep=plan.rep, method="exprace", acap=plan.acap,
                        massE=plan.massE[s])
        c = int(r.count)
        ref += _tuples(r.columns, keys, c)
        ref_pos += list(np.asarray(r.positions)[:c] + base)
        base += int(st.prefE[s, -1])
    assert got == ref
    np.testing.assert_array_equal(np.asarray(smp.positions)[:k], ref_pos)


def test_sharded_sample_statistics(db, query, mesh):
    engine = QueryEngine(db)
    plan = engine.compile_sharded(query, mesh, axes=("data",))
    single = engine.compile(query)
    cnts = [int(engine.sample(query, jax.random.key(i), mesh=mesh,
                              axes=("data",)).count) for i in range(40)]
    from repro.core import estimate
    exp = single.expected_k()
    sd = float(estimate.sample_std(single.w, single.p))
    z = (np.mean(cnts) - exp) / (sd / 40 ** 0.5)
    assert abs(z) < 4.5, (np.mean(cnts), exp, z)
    assert plan.expected_k() == pytest.approx(exp)


# -- (b) cache behavior -----------------------------------------------------

def test_sharded_warm_no_stacked_rebuild(db, query, mesh):
    engine = QueryEngine(db)
    engine.sample(query, jax.random.key(0), mesh=mesh, axes=("data",))
    st0 = engine.stats.snapshot()
    assert st0.shred_builds == 1
    # Warm: new draws, the other entry point, and a second mesh object of
    # the same shape all reuse the one stacked shred.
    engine.sample(query, jax.random.key(1), mesh=mesh, axes=("data",))
    engine.full_join(query, mesh=mesh, axes=("data",))
    mesh2 = jax.make_mesh((len(jax.devices()),), ("data",))
    engine.sample(query, jax.random.key(2), mesh=mesh2, axes=("data",))
    st1 = engine.stats
    assert st1.shred_builds == st0.shred_builds, \
        "warm sharded calls must not rebuild the stacked shred"
    assert st1.plan_hits >= 2
    # The single-device path is a *different* shred cache entry.
    engine.sample(query, jax.random.key(3))
    assert engine.stats.shred_builds == st0.shred_builds + 1


def test_sharded_empty_root(mesh):
    """A 0-row root partitions into 0-row shards; both entry points return
    empty, matching the single-device contract."""
    db0 = Database.from_columns({
        "R": {"x": np.zeros((0,), np.int64), "p": np.zeros((0,), np.float64)},
        "S": {"x": np.array([1, 2]), "y": np.array([3, 4])},
    })
    q = JoinQuery((Atom.of("R", "x", "p"), Atom.of("S", "x", "y")),
                  prob_var="p")
    engine = QueryEngine(db0)
    smp = engine.sample(q, jax.random.key(0), mesh=mesh, axes=("data",))
    assert int(smp.count) == 0 and not bool(smp.overflow)
    full = engine.full_join(q, mesh=mesh, axes=("data",))
    assert all(len(v) == 0 for v in full.values())


def test_sharded_auto_redraw_overflow(db, query, mesh):
    """A deliberately tiny capacity overflows; auto mode recovers."""
    engine = QueryEngine(db)
    s = engine.sample(query, jax.random.key(4), mesh=mesh, axes=("data",),
                      cap=1)
    assert bool(s.overflow)
    s = engine.sample(query, jax.random.key(4), mesh=mesh, axes=("data",),
                      auto=True)
    assert not bool(s.overflow)


# -- acceptance: real 8-device mesh (subprocess) ----------------------------

SCRIPT = textwrap.dedent("""
    import numpy as np, jax
    assert len(jax.devices()) == 8, jax.devices()
    from repro.core import Atom, Database, JoinQuery
    from repro.engine import QueryEngine, ShardedPlan

    rng = np.random.default_rng(11)
    db = Database.from_columns({
        "R": {"x": rng.integers(0, 12, 90), "p": rng.random(90) * 0.5},
        "S": {"x": rng.integers(0, 12, 140), "y": rng.integers(0, 9, 140)},
        "T": {"y": rng.integers(0, 9, 60), "z": np.arange(60)},
    })
    q = JoinQuery((Atom.of("R", "x", "p"), Atom.of("S", "x", "y"),
                   Atom.of("T", "y", "z")), prob_var="p")
    mesh = jax.make_mesh((8,), ("data",))
    engine = QueryEngine(db)
    plan = engine.compile_sharded(q, mesh)      # auto planner, real 8 shards
    assert isinstance(plan, ShardedPlan) and plan.num_shards == 8

    # The stacked index (and the plan's mass prefix) is placed one shard
    # per device, not left whole on the first device.
    st = plan.stacked
    for x in jax.tree.leaves((st.shred, st.w, st.p, st.prefE, plan.massE)):
        assert len(x.sharding.device_set) == 8, x.sharding
        assert x.sharding.shard_shape(x.shape)[0] == 1, x.sharding

    # Sharded sample == the single-device engine under the same
    # seed-folding scheme (one plain-engine draw per shard block).
    key = jax.random.key(3)
    smp = engine.sample(q, key, mesh=mesh)
    k = int(smp.count)
    keys = tuple(sorted(smp.columns))
    got = sorted(zip(*[np.asarray(smp.columns[v])[:k] for v in keys]))

    from repro.core.distributed import partition_root, semijoin_filter
    part = partition_root(semijoin_filter(db, q), q, 8)
    ref = []
    for s, sdb in enumerate(part.shards):
        # kernels="pernode": the sharded executors always run the per-node
        # route, so the per-shard reference must too — under a Pallas-
        # preferring policy a plain engine would auto-route to the fused
        # draw, whose stream is its own (DESIGN.md section 14).
        r = QueryEngine(sdb).sample(q, jax.random.fold_in(key, s),
                                    cap=plan.cap, acap=plan.acap,
                                    kernels="pernode")
        c = int(r.count)
        ref += list(zip(*[np.asarray(r.columns[v])[:c] for v in keys]))
    assert got == sorted(ref), (len(got), len(ref))

    # Warm path: zero stacked-shred rebuilds.
    before = engine.stats.shred_builds
    engine.sample(q, jax.random.key(4), mesh=mesh)
    engine.full_join(q, mesh=mesh)
    assert engine.stats.shred_builds == before
    print("SHARDED_ENGINE_OK")
""")


@pytest.mark.slow
def test_sharded_engine_8_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert "SHARDED_ENGINE_OK" in r.stdout
