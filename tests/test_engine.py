"""The unified query engine: correctness vs the pre-refactor paths, and
the compiled-plan / shred cache contract (DESIGN.md §7).

(a) full-join results bit-identical to the direct build_shred+flatten path;
(b) Poisson samples bit-identical to PoissonSampler under a fixed key;
(c) a second invocation with the same query fingerprint hits the plan
    cache — no shred rebuild (asserted by instrumenting build_shred).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    Atom, Database, JoinQuery, PoissonSampler, build_shred, yannakakis,
)
from repro.core.shred import build_shred as raw_build_shred
from repro.engine import (
    CapacityPolicy, QueryEngine, query_fingerprint, schema_fingerprint,
)


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


# -- (a) full join ----------------------------------------------------------

@pytest.mark.parametrize("rep", ["usr", "csr"])
def test_full_join_bit_identical_to_direct_path(db, query, rep):
    engine = QueryEngine(db, rep=rep)
    got = engine.full_join(query)
    shred = build_shred(db, query, rep=rep)       # the pre-engine path
    want = yannakakis.flatten(shred, rep=rep)
    assert set(got) == set(want)
    for v in want:
        np.testing.assert_array_equal(np.asarray(got[v]), np.asarray(want[v]))


@pytest.mark.filterwarnings("ignore:core.yannakakis.full_join is deprecated")
def test_full_join_facade_matches_engine(db, query):
    engine = QueryEngine(db)
    a = engine.full_join(query)
    b = yannakakis.full_join(db, query)
    for v in a:
        np.testing.assert_array_equal(np.asarray(a[v]), np.asarray(b[v]))


# -- (b) Poisson sampling ---------------------------------------------------

@pytest.mark.filterwarnings("ignore:core.PoissonSampler is deprecated")
def test_poisson_sample_bit_identical_to_sampler(db, query):
    engine = QueryEngine(db)
    sampler = PoissonSampler(db, query)
    for seed in range(4):
        key = jax.random.key(seed)
        a = engine.poisson_sample(query, key)
        b = sampler.sample(key)
        assert int(a.count) == int(b.count)
        np.testing.assert_array_equal(np.asarray(a.positions),
                                      np.asarray(b.positions))
        for v in b.columns:
            np.testing.assert_array_equal(np.asarray(a.columns[v]),
                                          np.asarray(b.columns[v]))


def test_poisson_sample_statistics(db, query):
    """Mean sample count matches the exact E[k] from the index."""
    engine = QueryEngine(db)
    plan = engine.compile(query)
    cnts = [int(engine.poisson_sample(query, jax.random.key(i)).count)
            for i in range(60)]
    from repro.core import estimate
    exp = plan.expected_k()
    sd = float(estimate.sample_std(plan.w, plan.p))
    z = (np.mean(cnts) - exp) / (sd / 60 ** 0.5)
    assert abs(z) < 4.5


def test_sample_membership(db, query):
    engine = QueryEngine(db)
    smp = engine.poisson_sample(query, jax.random.key(2), auto=True)
    v = np.asarray(smp.valid())
    full = engine.full_join(query)
    keys = tuple(sorted(full))
    fullset = set(zip(*[np.asarray(full[k]) for k in keys]))
    got = list(zip(*[np.asarray(smp.columns[k])[v] for k in keys]))
    assert len(got) == int(smp.count)
    assert all(t in fullset for t in got)


# -- (c) cache behavior -----------------------------------------------------

def test_warm_cache_no_shred_rebuild(db, query, monkeypatch):
    import repro.engine.engine as engmod

    calls = []

    def counting_build(d, q, rep="usr"):
        calls.append((query_fingerprint(q), rep))
        return raw_build_shred(d, q, rep=rep)

    monkeypatch.setattr(engmod, "build_shred", counting_build)
    engine = QueryEngine(db)

    engine.poisson_sample(query, jax.random.key(0))
    assert len(calls) == 1
    # Warm: same fingerprint — full join, sampling, join_size all reuse it.
    engine.poisson_sample(query, jax.random.key(1))
    engine.full_join(query)
    engine.join_size(query)
    assert len(calls) == 1, "warm-cache calls must not rebuild the index"
    assert engine.stats.shred_builds == 1
    assert engine.stats.plan_hits >= 2

    # An *equal but distinct* query object has the same fingerprint.
    query2 = JoinQuery(tuple(query.atoms), prob_var=query.prob_var)
    assert query_fingerprint(query2) == query_fingerprint(query)
    engine.poisson_sample(query2, jax.random.key(2))
    assert len(calls) == 1

    # A different rep is a different shred cache entry.
    engine.full_join(query, rep="csr")
    assert len(calls) == 2


def test_plan_cache_shared_across_methods(db, query, monkeypatch):
    """Two methods = two plans but ONE shred (same fingerprint+rep)."""
    import repro.engine.engine as engmod

    calls = []
    monkeypatch.setattr(
        engmod, "build_shred",
        lambda d, q, rep="usr": (calls.append(rep) or raw_build_shred(d, q, rep=rep)))
    engine = QueryEngine(db)
    engine.compile(query, method="exprace")
    engine.compile(query, method="ptbern_flat")
    assert engine.stats.plan_misses == 2
    assert calls == ["usr"]


def test_lru_eviction(db):
    engine = QueryEngine(db, max_plans=2)
    queries = [
        JoinQuery((Atom.of("R", "x", f"p{i}"), Atom.of("S", "x", "y")),
                  prob_var=f"p{i}")
        for i in range(3)
    ]
    for q in queries:
        engine.compile(q)
    assert len(engine._plans) == 2
    assert len(engine._shreds) == 2


def test_fingerprints():
    db = Database.from_columns({
        "R": {"x": [1, 2], "p": [0.5, 0.5]}, "S": {"x": [1], "y": [3]}})
    qa = JoinQuery((Atom.of("R", "x", "p"), Atom.of("S", "x", "y")),
                   prob_var="p")
    qb = JoinQuery((Atom.of("R", "x", "p"), Atom.of("S", "x", "y")))
    assert query_fingerprint(qa) != query_fingerprint(qb)  # prob_var matters
    db2 = Database.from_columns({
        "R": {"x": [1, 2, 3], "p": [0.5, 0.5, 0.5]}, "S": {"x": [1], "y": [3]}})
    assert schema_fingerprint(db) != schema_fingerprint(db2)  # row counts


def test_rebind_invalidates(db, query):
    engine = QueryEngine(db)
    engine.compile(query)
    assert len(engine._plans) == 1
    engine.rebind(db)
    assert len(engine._plans) == 0 and len(engine._shreds) == 0


def test_capacity_policy_is_engine_scoped(db, query):
    """A tighter policy produces smaller buffers; overflow still flagged."""
    tight = QueryEngine(db, policy=CapacityPolicy(sigmas=0.0, slack=0,
                                                  lane_multiple=1))
    loose = QueryEngine(db)
    pt = tight.compile(query)
    pl = loose.compile(query)
    assert pt.default_capacity() <= pl.default_capacity()
    s = loose.poisson_sample(query, jax.random.key(0), auto=True)
    assert not bool(s.overflow)


def test_uniform_sample_via_engine(db, query):
    engine = QueryEngine(db)
    n = engine.join_size(query)
    smp = engine.uniform_sample(query, jax.random.key(5), 0.1)
    k = int(smp.count)
    assert 0 <= k <= smp.capacity
    pos = np.asarray(smp.positions)[:k]
    assert (pos >= 0).all() and (pos < n).all()


def test_prob_var_required_for_poisson(db):
    q = JoinQuery((Atom.of("R", "x", "p"), Atom.of("S", "x", "y")))
    engine = QueryEngine(db)
    with pytest.raises(ValueError, match="prob_var"):
        engine.poisson_sample(q, jax.random.key(0))
    # ... but full_join on the same query is fine.
    full = engine.full_join(q)
    assert len(next(iter(full.values()))) == engine.join_size(q)


def test_tpu_search_route_is_bit_identical(db, query, monkeypatch):
    """The TPU's row-gather search (``ops.kary_searchsorted``), put in
    place of XLA's binary search on every engine path, changes no result:
    full join, single draws and a batch, bit for bit."""
    from repro.kernels import ops

    def run():
        eng = QueryEngine(db)
        keys = jax.random.split(jax.random.key(3), 4)
        return (eng.full_join(query),
                [eng.poisson_sample(query, jax.random.key(s)) for s in (0, 1)],
                eng.sample_batch(query, keys))

    want = run()
    monkeypatch.setattr(ops, "searchsorted",
                        lambda t, q, side="right": ops.kary_searchsorted(
                            t, q, side, fan=8, chunk=64))
    got = run()
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- EXPRACE's int32 certificate (DESIGN.md §4) ------------------------------

def _leaves_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_unpacked_shred_binds_narrow(db, query):
    """The join size certifies int32, not the packed arena: a plan over a
    shred with neither arena nor pages still narrows, and draws what the
    packed plan draws."""
    import dataclasses

    from repro.engine.plan import CompiledPlan

    plan = QueryEngine(db).compile(query)
    bare = dataclasses.replace(plan.shred, packed=None, paged=None)
    unpacked = CompiledPlan(query, plan.spec, bare)
    assert plan.narrow and unpacked.narrow
    key = jax.random.key(2)
    _leaves_equal(unpacked.sample(key), plan.sample(key))


def test_narrow_off_draws_the_same_sample(db, query):
    engine = QueryEngine(db)
    narrow, wide = engine.compile(query), engine.compile(query, narrow=False)
    assert narrow.narrow and not wide.narrow
    for s in range(3):
        key = jax.random.key(s)
        _leaves_equal(narrow.sample(key), wide.sample(key))
    keys = jax.random.split(jax.random.key(9), 3)
    _leaves_equal(narrow.sample_batch(keys), wide.sample_batch(keys))


@pytest.fixture
def past_int32(monkeypatch):
    """The certificate as it reads a join 2^31 tuples larger than the one
    built, so a small join fails it."""
    from repro.core import sampling

    fits = sampling.exprace_fits_int32
    monkeypatch.setattr(sampling, "exprace_fits_int32",
                        lambda n, R, acap, cap=0: fits(n + (1 << 31), R,
                                                       acap, cap))


def test_forced_narrow_raises_when_the_certificate_fails(db, query,
                                                         past_int32):
    with pytest.raises(ValueError, match="narrow=True"):
        QueryEngine(db).compile(query, narrow=True)


def test_joins_past_int32_trace_the_int64_program(db, query, past_int32,
                                                  monkeypatch):
    from repro import obs
    from repro.core import sampling

    traced = []
    positions = sampling.exprace_positions

    def spy(*args, narrow=False, **kw):
        traced.append(narrow)
        return positions(*args, narrow=narrow, **kw)

    monkeypatch.setattr(sampling, "exprace_positions", spy)
    plan = QueryEngine(db).compile(query)
    assert not plan.narrow
    with obs.record() as rec:
        plan.sample(jax.random.key(4))
    assert traced == [False]
    assert rec.counters["draws"] == 1 and "draws_int32" not in rec.counters


@pytest.mark.parametrize("override,n,fits", [
    (None, (1 << 31) - 2 - 90, True),    # n + R + 1 = 2^31 - 1, R = 90
    (None, (1 << 31) - 1 - 90, False),   # n + R + 1 = 2^31
    (False, 1000, False),
    (True, 1000, True),
])
def test_resolve_narrow(override, n, fits):
    from repro.engine.plan import resolve_narrow

    assert resolve_narrow(override, n, 90, 4096) is fits


def test_resolve_narrow_refuses_a_forced_width_past_int32():
    from repro.engine.plan import resolve_narrow

    with pytest.raises(ValueError, match="2\\^31"):
        resolve_narrow(True, (1 << 31) - 1 - 90, 90, 4096)
