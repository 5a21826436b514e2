"""The program's spans, counters and device scopes (``repro.obs``): records
nest and give self time, nothing is kept without a record, spans reach a
profiler trace by name, and every scope that ``obs.SCOPES`` lists is in
the op-name metadata of the executables that should carry it."""
import glob
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs


@pytest.fixture
def clock(monkeypatch):
    """``perf_counter_ns`` stepping 1 ns a read, so durations count reads."""
    ticks = itertools.count()
    monkeypatch.setattr(obs.time, "perf_counter_ns", lambda: next(ticks))


def test_names_are_unique_and_apart():
    assert len(set(obs.SPANS)) == len(obs.SPANS)
    assert len(set(obs.SCOPES)) == len(obs.SCOPES)
    assert not set(obs.SPANS) & set(obs.SCOPES)


def test_records_nest_and_give_self_time(clock):
    with obs.record() as rec:
        with obs.span("shred_build"):            # t0 = 0
            with obs.span("shred_keys"):         # 1 .. 2
                pass
            with obs.span("shred_sort"):         # 3 .. 6
                with obs.span("shred_place"):    # 4 .. 5
                    pass
        obs.count("draws", 2)                    # build closed at 7
        obs.count("draws")
    names = [(s.name, s.parent) for s in rec.spans]
    assert names == [("shred_build", None), ("shred_keys", 0),
                     ("shred_sort", 0), ("shred_place", 2)]
    assert [(s.t0, s.t1) for s in rec.spans] == [(0, 7), (1, 2), (3, 6),
                                                 (4, 5)]
    assert rec.seconds("shred_build") == pytest.approx(7e-9)
    # 7 less its children's 1 and 3; the grandchild is not subtracted twice
    assert rec.self_seconds("shred_build") == pytest.approx(3e-9)
    assert rec.self_seconds("shred_sort") == pytest.approx(2e-9)
    assert rec.self_seconds("shred_place") == pytest.approx(1e-9)
    assert rec.seconds("plan_bind") == 0.0
    assert rec.counters == {"draws": 3}


def test_self_time_of_repeated_spans_and_overlapping_children():
    rec = obs.Record([obs.Span("a", None, 0, 10), obs.Span("b", 0, 2, 6),
                      obs.Span("b", 0, 4, 8), obs.Span("a", None, 20, 25)])
    assert rec.seconds("a") == pytest.approx(15e-9)
    # children of the first cover 2..8 (6 ns): 4 + 5 ns of self time
    assert rec.self_seconds("a") == pytest.approx(9e-9)
    assert rec.seconds("b") == pytest.approx(8e-9)


def test_nothing_is_recorded_without_a_record():
    with obs.span("engine_sample", route="pernode"):
        obs.count("draws", 5)
    with obs.record() as rec:
        pass
    assert rec.spans == [] and rec.counters == {}
    with obs.record() as outer:
        with obs.record() as inner:
            with obs.span("plan_bind"):
                obs.count("draws")
        with obs.span("shred_build"):
            pass
    assert [s.name for s in inner.spans] == ["plan_bind"]
    assert inner.counters == {"draws": 1}
    assert [s.name for s in outer.spans] == ["shred_build"]
    assert outer.counters == {}


def test_a_span_closes_when_its_block_raises():
    with obs.record() as rec:
        with pytest.raises(ValueError):
            with obs.span("plan_bind"):
                raise ValueError("boom")
        with obs.span("shred_build"):
            pass
    assert [(s.name, s.parent) for s in rec.spans] == [
        ("plan_bind", None), ("shred_build", None)]
    assert all(s.t1 is not None for s in rec.spans)


def test_spans_reach_a_profiler_trace_by_name(tmp_path):
    from jax.profiler import ProfileData

    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with obs.span("plan_draw", route="pernode", cap=64, acap=96, batch=1):
        with obs.span("batcher_wait"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in ("plan_draw", "batcher_wait"):
                    found[e.name] = (e.start_ns, e.end_ns, dict(e.stats))
    assert set(found) == {"plan_draw", "batcher_wait"}
    s0, e0, args = found["plan_draw"]
    s1, e1, _ = found["batcher_wait"]
    assert s0 <= s1 and e1 <= e0
    assert args["route"] == "pernode" and int(args["cap"]) == 64


def _op_names(compiled_text: str):
    import re
    return re.findall(r'op_name="([^"]*)"', compiled_text)


def _scopes_in(text: str):
    parts = {p for n in _op_names(text) for p in n.split("/")}
    return {s for s in obs.SCOPES if s in parts}


@pytest.fixture(scope="module")
def job_plan():
    from benchmarks.workloads import job_like
    from repro.engine import QueryEngine

    db, q = job_like(seed=0, scale=60)
    engine = QueryEngine(db)
    return engine, q, engine.compile(q)


def test_the_draw_executor_carries_every_draw_scope(job_plan):
    """A refactor that drops a scope from the draw's path fails here."""
    _, _, plan = job_plan
    args = (plan.shred, plan.w, plan.p, plan.prefE, jax.random.key(0))
    text = plan._jit.lower(
        *args, cap=plan.default_capacity(), rep="usr", n=0,
        acap=plan.arrival_capacity(), narrow=plan.narrow, route="pernode",
        dparams=None, massE=plan._massE).compile().as_text()
    assert _scopes_in(text) == {
        "exprace", "exprace_arrivals", "exprace_dedupe", "exprace_compact",
        "exprace_emit", "usr_locate", "usr_gather", "usr_gather_bits",
        "search"}
    # stages and searches nest under their phase
    names = _op_names(text)
    assert any("/exprace/exprace_dedupe/" in n for n in names)
    assert any("/usr_locate/search/" in n for n in names)
    assert any("/usr_gather/usr_gather_bits/" in n for n in names)


def test_scopes_change_no_result(job_plan, monkeypatch):
    """A scope names ops only: with every ``named_scope`` made a no-op,
    the same key draws the same sample, bit for bit."""
    import contextlib

    from repro.engine import executors

    _, _, plan = job_plan
    kw = dict(cap=plan.default_capacity(), rep="usr", n=0,
              acap=plan.arrival_capacity(), narrow=plan.narrow,
              route="pernode", dparams=None, massE=plan._massE)
    args = (plan.shred, plan.w, plan.p, plan.prefE, jax.random.key(7))
    scoped = executors.sample_executor("exprace", None)(*args, **kw)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = executors.sample_executor("exprace", None)(*args, **kw)
    for a, b in zip(jax.tree.leaves(scoped), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_engine_calls_open_their_spans_and_count_draws(job_plan):
    engine, q, plan = job_plan
    with obs.record() as rec:
        engine.poisson_sample(q, jax.random.key(1))
        engine.sample_batch(q, jax.random.split(jax.random.key(2), 3))
        engine.full_join(q)
    top = [s.name for s in rec.spans if s.parent is None]
    assert top == ["engine_sample", "engine_sample_batch",
                   "engine_full_join"]
    # one executor call under each draw entry point
    assert [(rec.spans[s.parent].name, s.name) for s in rec.spans
            if s.parent is not None] == [("engine_sample", "plan_draw"),
                                         ("engine_sample_batch", "plan_draw")]
    acap = plan.arrival_capacity()
    # three keys run as a bucket of four lanes; the join certifies int32;
    # p, float64, is carried as bit patterns in each of the four draws
    assert rec.counters == {"draws": 4, "draws_int32": 4,
                            "draw_arrival_lanes": acap * 5,
                            "draw_bit_columns": 4}


def test_redraws_count_each_executor_call_at_its_capacity(job_plan):
    """An overflowing draw is drawn again at doubled capacities: each
    executor call counts as a draw, at the arrival capacity it ran at."""
    engine, q, plan = job_plan
    acap = plan.arrival_capacity()
    with obs.record() as rec:
        smp = engine.poisson_sample(q, jax.random.key(3), cap=2, auto=True)
    calls = [s for s in rec.spans if s.name == "plan_draw"]
    assert not bool(smp.overflow) and len(calls) >= 2
    assert rec.counters == {"draws": len(calls), "draws_int32": len(calls),
                            "draw_arrival_lanes": acap * (2 ** len(calls)
                                                          - 1),
                            "draw_bit_columns": len(calls)}


def test_a_sharded_draw_counts_the_lanes_of_every_shard(job_plan):
    from repro.engine.sharding import ShardedPlan

    engine, q, _ = job_plan
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    plan = engine.compile_sharded(q, mesh, axes=("data",))
    assert isinstance(plan, ShardedPlan)
    with obs.record() as rec:
        engine.poisson_sample(q, jax.random.key(4), mesh=mesh,
                              axes=("data",))
        engine.sample_batch(q, jax.random.split(jax.random.key(5), 3),
                            mesh=mesh, axes=("data",))
    assert [s.name for s in rec.spans] == [
        "engine_sample", "plan_draw", "engine_sample_batch", "plan_draw"]
    assert rec.counters == {
        "draws": 4, "draws_int32": 4,
        "draw_arrival_lanes": plan.acap * plan.num_shards * 5,
        "draw_bit_columns": 4}


def test_draws_int32_counts_only_narrowed_draws(job_plan):
    """With int32 forced off, no call counts ``draws_int32``; the plan
    that certifies it counts every draw, a batch's as ``draws`` does."""
    engine, q, plan = job_plan
    wide = engine.compile(q, narrow=False)
    assert plan.narrow and not wide.narrow
    with obs.record() as off:
        wide.sample(jax.random.key(6))
        wide.sample_batch(jax.random.split(jax.random.key(7), 3))
    assert "draws_int32" not in off.counters and off.counters["draws"] == 4
    with obs.record() as on:
        plan.sample_batch(jax.random.split(jax.random.key(7), 5))
    assert on.counters["draws_int32"] == on.counters["draws"] == 5


def test_plan_draw_span_carries_narrow(job_plan, tmp_path):
    from jax.profiler import ProfileData

    engine, q, plan = job_plan
    wide = engine.compile(q, narrow=False)
    key = jax.random.key(8)
    for p in (plan, wide):  # warm: compiles stay out of the trace
        p.sample(key).positions.block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for p in (plan, wide):
        p.sample(key).positions.block_until_ready()
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    seen = [dict(e.stats)["narrow"]
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events
            if e.name == "plan_draw"]
    assert [int(v) for v in seen] == [1, 0]  # a bool arg reads as 1 or 0


def test_index_build_opens_its_passes(job_plan):
    from benchmarks.workloads import job_like
    from repro.engine import QueryEngine

    db, q = job_like(seed=1, scale=60)
    with obs.record() as rec:
        QueryEngine(db).compile(q)
    top = [s.name for s in rec.spans if s.parent is None]
    assert top == ["shred_build", "plan_bind"]
    build = rec.spans[0]
    kids = {s.name for s in rec.spans if s.parent == 0}
    assert {"shred_keys", "shred_sort", "shred_link", "shred_place",
            "shred_pack"} <= kids
    covered = sum(rec.seconds(n) for n in (
        "shred_keys", "shred_sort", "shred_link", "shred_place",
        "shred_pack"))
    assert covered <= build.seconds + 1e-9


def test_batcher_flush_opens_its_steps(job_plan):
    from repro.launch.fleet.batcher import JoinSampleRequest, MicroBatcher

    engine, q, _ = job_plan
    mb = MicroBatcher(engine, max_batch=8, collect_rows=True)
    with obs.record() as rec:
        for s in range(3):
            mb.submit(JoinSampleRequest(q, seed=s))
        done = mb.flush()
    assert len(done) == 3 and mb.dispatches == 1 and mb.flushes == 1
    flush = [i for i, s in enumerate(rec.spans) if s.name == "batcher_flush"]
    assert len(flush) == 1
    kids = [s.name for s in rec.spans if s.parent == flush[0]]
    assert kids == ["batcher_keys", "batcher_dispatch", "batcher_wait",
                    "batcher_copy_out"]
    dispatch = [i for i, s in enumerate(rec.spans)
                if s.name == "batcher_dispatch"][0]
    assert [s.name for s in rec.spans if s.parent == dispatch] == [
        "engine_sample_batch"]
    assert rec.counters["draws"] == 3


def test_every_name_is_in_the_perf_layers_table():
    """PERF.md section 3 names each span and scope beside what reads it."""
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "PERF.md")) as f:
        text = f.read()
    layers = text[text.index("## 3. Layers"):text.index("## 4. Cells")]
    missing = [n for n in obs.SPANS + obs.SCOPES if f"`{n}`" not in layers]
    assert not missing
