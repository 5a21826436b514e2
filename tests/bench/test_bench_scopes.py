"""The program's names in a profiler trace (``bench/scopes.py``): scope
paths from op-name metadata, the attribution of device ops to scopes, the
program's host spans kept beside the benchmark's and naming idle gaps, and
the index build's passes from the program's record."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import json

import pytest

from bench import breakdown, scopes, tracing
from bench.scopes import ScopedTrace
from repro import obs

HERE = os.path.dirname(os.path.abspath(__file__))


def test_scope_paths_keep_program_scopes_in_order():
    assert scopes.scope_path(
        "jit(<unknown>)/exprace/exprace_dedupe/search/search/while") == (
        "exprace", "exprace_dedupe", "search", "search")
    assert scopes.scope_path(
        "jit(f)/vmap(usr_locate)/jit(searchsorted)/gather") == (
        "usr_locate",)
    assert scopes.scope_path("jit(f)/jit(sort)/sort") == ()
    assert scopes.scope_path("") == ()
    assert scopes.scope_path("a/exprace", scopes=("a",)) == ("a",)


def test_hlo_op_names_are_keyed_as_the_trace_names_ops():
    text = """HloModule jit_f
ENTRY %main.9 (x.1: s64[8]) -> s64[8] {
  %x.1 = s64[8]{0} parameter(0)
  %sort.0 = s64[8]{0:T(1024)} sort(%x.1), dimensions={0}, metadata={op_name="jit(f)/exprace/exprace_dedupe/jit(sort)/sort" stack_frame_id=2}
  %while.3 = (s32[], s64[8]{0}) while(%t), condition=%c, body=%b, metadata={op_name="jit(f)/usr_locate/search/while"}
  ROOT %fusion.1 = s64[8]{0} fusion(%sort.0), kind=kLoop, calls=%fc
}
"""
    got = scopes.hlo_op_names(text)
    assert got == {
        "x.1 s64[8]": "",
        "sort.0 s64[8]": "jit(f)/exprace/exprace_dedupe/jit(sort)/sort",
        "while.3": "jit(f)/usr_locate/search/while",
        "fusion.1 s64[8]": ""}
    assert tracing.op_name(
        "%sort.0 = s64[8]{0:T(1024)} sort(s64[8]{0} %x.1)") in got


def test_live_executables_name_the_scopes_of_a_jitted_program():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("exprace"):
            with jax.named_scope("exprace_dedupe"):
                y = jnp.sort(x)
        with jax.named_scope("usr_gather"):
            return y * 3

    f(jnp.arange(16.0)[::-1]).block_until_ready()
    mods = scopes.live_op_names()
    paths = {scopes.scope_path(v) for m in mods.values() for v in m.values()}
    assert ("exprace", "exprace_dedupe") in paths
    assert ("usr_gather",) in paths


def _synthetic():
    # window 0..100; two draws, 0..40 and 50..95, with make_keys 0..4
    # inside the first. Device ops: an EXPRACE while loop 5..20 holding
    # its own body 6..10 (a search), a locate 20..30, a gather 28..38,
    # an op in no scope 38..40, then the second draw's ops.
    ex = "jit(f)/exprace/exprace_dedupe"
    return {
        "device": {"/device:TPU:0": [
            ["while.1", 5, 15, ex + "/search/while"],
            ["fusion.2 u32[8]", 6, 4, ex + "/search/while/body/add"],
            ["fusion.3 u32[8]", 20, 10, "jit(f)/usr_locate/search/gather"],
            ["fusion.4 u32[8]", 28, 10, "jit(f)/usr_gather/gather"],
            ["copy.5 u32[8]", 38, 2, ""],
            ["fusion.6 u32[8]", 55, 20, ex + "/add"],
            ["fusion.7 u32[8]", 75, 15, "jit(f)/usr_gather/gather"]]},
        "host": [["window", 0, 100], ["draw", 0, 40], ["make_keys", 0, 4],
                 ["engine_sample", 4, 36], ["draw", 50, 45],
                 ["engine_sample", 52, 43]]}


def test_scope_attribution_from_a_synthetic_trace():
    st = ScopedTrace.from_events(_synthetic())
    assert st.has_scopes()
    # exprace: 5..20 (the loop holds its body: 15, not 19) and 55..75 (20)
    assert st.scope_device_s("exprace") == pytest.approx(17.5e-9)
    assert st.scope_device_s("exprace_dedupe") == pytest.approx(17.5e-9)
    # search: 5..20 under exprace and 20..30 under the locate
    assert st.scope_device_s("search") == pytest.approx(12.5e-9)
    assert st.scope_device_s("usr_locate") == pytest.approx(5e-9)
    assert st.scope_device_s("usr_gather") == pytest.approx(12.5e-9)
    assert st.unscoped_device_s() == pytest.approx(1e-9)
    assert st.scope_device_s("exprace_emit") == 0.0
    # the phases and the unscoped part sum to the draw's device time and
    # the 2 ns in which the locate and the gather overlap (1 ns a draw)
    phases = sum(st.scope_device_s(s) for s in ("exprace", "usr_locate",
                                                "usr_gather"))
    assert phases + st.unscoped_device_s() == pytest.approx(
        st.span_device_s("draw") + 1e-9)
    assert st.scope_device_s("exprace", under="schedule") is None
    top = dict(st.device_scopes())
    assert top["search"] == pytest.approx(25e-9)   # 5..30, innermost
    assert top["exprace_dedupe"] == pytest.approx(20e-9)
    assert top["usr_gather"] == pytest.approx(25e-9)
    assert top["none"] == pytest.approx(2e-9)


def test_a_plain_trace_reads_scopes_by_op_name():
    ev = _synthetic()
    names = {e[0]: e[3] for e in ev["device"]["/device:TPU:0"]}
    plain = tracing.Trace.from_events(
        {"device": {p: [e[:3] for e in evs]
                    for p, evs in ev["device"].items()},
         "host": ev["host"]})
    st = ScopedTrace.of(plain, names)
    assert st.scope_device_s("exprace") == pytest.approx(17.5e-9)
    assert not ScopedTrace.of(plain, {}).has_scopes()


def test_readers_give_none_without_program_scopes(monkeypatch):
    ev = _synthetic()
    plain = tracing.Trace.from_events(
        {"device": {p: [e[:3] for e in evs]
                    for p, evs in ev["device"].items()},
         "host": ev["host"]})
    monkeypatch.setattr(scopes, "live_op_names", lambda: {})
    assert scopes.draw_scope_s(plain, "exprace") is None
    assert scopes.draw_scope_s(None, "exprace") is None
    monkeypatch.setattr(scopes, "live_op_names", lambda: {"jit_f": {
        e[0]: e[3] for e in ev["device"]["/device:TPU:0"]}})
    assert scopes.draw_scope_s(plain, "usr_gather") == pytest.approx(12.5e-9)


def test_names_two_modules_disagree_on_get_no_scope():
    modules = {
        "jit_f": {"fusion.1 u32[8]": "jit(f)/exprace/add",
                  "copy.2": "jit(f)/x", "while.3": "jit(f)/search/while"},
        "jit_g": {"fusion.1 u32[8]": "jit(g)/usr_gather/add",
                  "copy.2": "jit(g)/y", "while.3": "jit(g)/search/while"}}
    merged = scopes._merge(modules.values())
    assert merged["fusion.1 u32[8]"] == ""
    assert scopes.scope_path(merged["copy.2"]) == ()
    assert scopes.scope_path(merged["while.3"]) == ("search",)
    # breakdown counts the scoped names the merge blanked
    assert breakdown.blanked(modules) == 1


def test_gaps_are_named_by_the_program_spans_of_a_flush():
    # a flush (the benchmark's span) holds the batcher's own steps: keys
    # 10..20, dispatch 20..25 (its draw runs on the device 22..60), wait
    # 25..60, copy-out 60..80.
    ev = {"device": {"/device:TPU:0": [["fusion.1 u32[8]", 22, 38,
                                        "jit(f)/exprace/add"]]},
          "host": [["window", 0, 100], ["flush", 5, 80], ["draw", 20, 40],
                   ["batcher_flush", 8, 76], ["batcher_keys", 10, 10],
                   ["batcher_dispatch", 20, 5], ["batcher_wait", 25, 35],
                   ["batcher_copy_out", 60, 20]]}
    st = ScopedTrace.from_events(ev)
    gaps = st.idle_gaps()
    assert gaps == [["batcher_copy_out", pytest.approx(40e-9)],
                    ["batcher_keys", pytest.approx(22e-9)]]
    # without the program's spans the benchmark's guess names them
    plain = tracing.Trace.from_events(
        {"device": {"/device:TPU:0": [e[:3] for e in
                                      ev["device"]["/device:TPU:0"]]},
         "host": [h for h in ev["host"] if h[0] in tracing.SPAN_NAMES]})
    assert [n for n, _ in plain.idle_gaps()] == ["copy_out", "make_keys"]


def _xspace(tmp_path, ops, modules):
    """A serialized XSpace with one TPU plane, as the chip writes it:
    "XLA Modules" events named ``<module>(<id>)``, "XLA Ops" events named
    by their HLO text, and no module on an op."""
    from jax.profiler import ProfileData

    meta, mod_ev, op_ev = [], [], []
    for i, (name, start, dur) in enumerate(modules, 1):
        meta.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{name}" }} }}')
        mod_ev.append(f"events {{ metadata_id: {i} offset_ps: {start * 1000}"
                      f" duration_ps: {dur * 1000} }}")
    for j, (text, start, dur) in enumerate(ops, len(modules) + 1):
        meta.append(f"event_metadata {{ key: {j} value {{ id: {j} "
                    f"name: {json.dumps(text)} }} }}")
        op_ev.append(f"events {{ metadata_id: {j} offset_ps: {start * 1000}"
                     f" duration_ps: {dur * 1000} }}")
    text = ("planes { id: 1 name: \"/device:TPU:0\" "
            f"lines {{ id: 1 name: \"XLA Modules\" timestamp_ns: 0 "
            f"{' '.join(mod_ev)} }} "
            f"lines {{ id: 2 name: \"XLA Ops\" timestamp_ns: 0 "
            f"{' '.join(op_ev)} }} {' '.join(meta)} }} "
            "planes { id: 2 name: \"/host:CPU\" lines { id: 3 name: "
            "\"python\" timestamp_ns: 0 events { metadata_id: 1 "
            "offset_ps: 0 duration_ps: 90000 } events { metadata_id: 2 "
            "offset_ps: 1000 duration_ps: 80000 } events { metadata_id: 3 "
            "offset_ps: 2000 duration_ps: 1000 } } event_metadata { key: 1 "
            "value { id: 1 name: \"window\" } } event_metadata { key: 2 "
            "value { id: 2 name: \"engine_sample\" } } event_metadata { "
            "key: 3 value { id: 3 name: \"not_a_span\" } } }")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def test_tpu_ops_take_the_scope_of_the_module_that_ran_them(tmp_path):
    # fusion.1 runs twice: in the scoped module and in another one that
    # has an op of the same name and shape, at 60.
    path = _xspace(tmp_path, [
        ("%fusion.1 = u32[8]{0:T(1024)} fusion(u32[8]{0} %p)", 10, 20),
        ("%while.2 = (s32[], u32[8]{0}) while((s32[], u32[8]{0}) %t)", 30,
         10),
        ("%fusion.1 = u32[8]{0:T(1024)} fusion(u32[8]{0} %q)", 60, 5),
        ("%copy.3 = u32[8]{0} copy(u32[8]{0} %r)", 80, 5)],
        [("jit__unknown(1531250744604669859)", 5, 45),
         ("jit_other(77)", 55, 20)])
    modules = {"jit__unknown": {
        "fusion.1 u32[8]": "jit(<unknown>)/exprace/exprace_emit/add",
        "while.2": "jit(<unknown>)/usr_locate/search/while"}}
    ev = scopes.events_from_xplane(path, modules)
    ops = ev["device"]["/device:TPU:0"]
    assert [(o[0], o[3]) for o in ops] == [
        ("fusion.1 u32[8]", "jit(<unknown>)/exprace/exprace_emit/add"),
        ("while.2", "jit(<unknown>)/usr_locate/search/while"),
        ("fusion.1 u32[8]", ""),     # another module: no program scope
        ("copy.3 u32[8]", "")]       # in no module
    assert sorted(h[0] for h in ev["host"]) == ["engine_sample", "window"]
    # the benchmark's own reduction keeps only its spans, as before
    assert [h[0] for h in tracing.events_from_xplane(path)["host"]] == [
        "window"]
    st = ScopedTrace.from_events(ev)
    assert st.device_scopes()[0] == ["exprace_emit", pytest.approx(20e-9)]


def test_the_set_up_passes_come_from_a_record_over_a_small_build():
    from bench import harness

    cell = harness.resolve("job-star-p32.draw")
    rows = {"title": 300, "cast_info": 4300, "movie_companies": 310}
    with obs.record() as rec:
        sess = harness.build_session(cell, 2**31 + 11, rows)
    split = breakdown.setup_split(rec, sess.index_build_s)
    for k in ("index_group_s", "index_place_s", "index_pack_s",
              "plan_bind_s"):
        assert split[k] > 0, k
    assert 0 < split["covered_share"] <= 100.0
    # two edges' grouping passes, one build and one bind for one shape
    assert sum(s.name == "shred_sort" for s in rec.spans) == 2
    assert sum(s.name == "shred_build" for s in rec.spans) == 1
    assert sum(s.name == "plan_bind" for s in rec.spans) == 1


def test_an_excerpt_is_cut_at_the_first_draw_phases_and_its_end():
    ev = _synthetic()
    # 4 ns slices around the locate's first op (20), the gather's (28)
    # and the draw's end (40)
    slices = breakdown.excerpt(ev, ms=4e-6)
    assert [sl["host"][0] for sl in slices] == [
        ["window", 18.0, 4.0], ["window", 26.0, 4.0], ["window", 38.0, 4.0]]
    names = [[e[0] for e in sl["device"]["/device:TPU:0"]] for sl in slices]
    assert names == [["while.1", "fusion.3 u32[8]"],
                     ["fusion.3 u32[8]", "fusion.4 u32[8]"],
                     ["copy.5 u32[8]"]]
    st = ScopedTrace.from_events(slices[1])
    assert st.window() == (26.0, 30.0)
    assert st.busy_s() == pytest.approx(4e-9)
    assert st.scope_device_s("usr_gather") == pytest.approx(2e-9)


def _sweep(intervals, lo, hi):
    """Independent busy time: walk sorted endpoints, counting open ops."""
    points = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            points += [(s, 1), (e, -1)]
    points.sort()
    busy, depth, last = 0.0, 0, None
    for t, d in points:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_recorded_scoped_trace_from_the_chip():
    """Slices of a traced ``job-star-p32.draw`` run on a TPU v5 lite, each
    op with the op-name metadata of the executable that ran it, reduced
    to device seconds per program scope."""
    with open(os.path.join(HERE, "data", "trace_draw_scopes.json")) as f:
        rec = json.load(f)
    seen = set()
    for sl in rec["slices"]:
        st = ScopedTrace.from_events(sl)
        want = sl["expected"]
        ops = sl["device"]["/device:TPU:0"]
        lo, hi = st.window()
        assert st.busy_s() == pytest.approx(want["busy_s"])
        assert st.busy_s() == pytest.approx(
            _sweep([(s, s + d) for _, s, d, _ in ops], lo, hi) / 1e9)
        draws = st.spans("draw")
        for scope, value in want["scopes"].items():
            got = st.scope_device_s(scope)
            assert got == pytest.approx(value), scope
            if got:
                mine = [(s, s + d) for _, s, d, m in ops
                        if scope in scopes.scope_path(m)]
                assert got == pytest.approx(sum(
                    _sweep(mine, a, b) for a, b in draws) / len(draws) / 1e9)
                seen.add(scope)
        assert st.unscoped_device_s() == pytest.approx(want["unscoped_s"])
        assert st.device_scopes() == [[n, pytest.approx(t)]
                                      for n, t in want["device_scopes"]]
    # the slices hold the draw's phases and their searches as the chip
    # ran them
    assert {"exprace", "usr_locate", "usr_gather", "search"} <= seen
