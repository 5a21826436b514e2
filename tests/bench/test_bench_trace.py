"""The reduction from a profiler trace to device numbers: busy union, idle
share, device time per span, and the breakdown."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import json
import os

import pytest

from bench import tracing
from bench.tracing import Trace

HERE = os.path.dirname(os.path.abspath(__file__))


def _sweep_busy(intervals, lo, hi):
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


def test_hand_counted_trace():
    # window 0..100 ns; ops overlap at 10..30 and 20..40, then 60..70 and
    # a piece past the window's end; spans: draw 0..45 (with make_keys
    # 0..5 inside), flush 46..100 (its draw 55..75).
    ev = {"device": {"/device:TPU:0": [["a", 10, 20], ["b", 20, 20],
                                       ["c", 60, 10], ["d", 95, 30]]},
          "host": [["window", 0, 100], ["draw", 0, 45], ["make_keys", 0, 5],
                   ["flush", 46, 54], ["draw", 55, 20]]}
    tr = Trace.from_events(ev)
    assert tr.window_s() == pytest.approx(100e-9)
    # busy: 10..40 (30) + 60..70 (10) + 95..100 (5) = 45 ns
    assert tr.busy_s() == pytest.approx(45e-9)
    assert tr.idle_share() == pytest.approx(55.0)
    # draw spans: 0..45 covers 30 ns, 55..75 covers 10 ns -> mean 20 ns
    assert tr.span_device_s("draw") == pytest.approx(20e-9)
    assert tr.span_device_s("make_keys") == pytest.approx(0.0)
    assert tr.span_device_s("schedule") is None
    top = tr.top_ops(2)
    assert [n for n, _ in top] == ["a", "b"]
    assert top[0][1] == pytest.approx(20e-9)
    # gaps: 0..10 (10, midpoint 5 in make_keys), 40..60 (20, midpoint 50
    # in the flush before its draw -> make_keys), 70..95 (25, midpoint
    # 82.5 in the flush after its draw -> copy_out)
    gaps = tr.idle_gaps()
    assert gaps == [["copy_out", pytest.approx(25e-9)],
                    ["make_keys", pytest.approx(20e-9)],
                    ["make_keys", pytest.approx(10e-9)]]


def test_recorded_trace_from_the_chip():
    with open(os.path.join(HERE, "data", "trace_draw_boundary.json")) as f:
        ev = json.load(f)
    tr = Trace.from_events(ev)
    lo, hi = tr.window()
    ops = [(s, s + d) for _, s, d in ev["device"]["/device:TPU:0"]]
    busy = _sweep_busy(ops, lo, hi)
    assert tr.busy_s() == pytest.approx(busy / 1e9)
    assert tr.idle_share() == pytest.approx(100 * (1 - busy / (hi - lo)))
    draws = tr.spans("draw")
    assert len(draws) == 2
    per = [_sweep_busy(ops, s, e) for s, e in draws]
    assert tr.span_device_s("draw") == pytest.approx(sum(per) / 2 / 1e9)
    want = ev["expected"]
    assert tr.busy_s() == pytest.approx(want["busy_s"])
    assert [n for n, _ in tr.top_ops(3)] == [n for n, _ in want["top_ops"]]
    assert tr.idle_gaps(3) == [[n, pytest.approx(s)]
                               for n, s in want["idle_gaps"]]
    # the gap between two draws is the host making the next key
    assert "make_keys" in [n for n, _ in tr.idle_gaps(3)]


def test_op_names_are_cut_to_name_and_shape():
    assert tracing.op_name(
        "%fusion.76 = u32[7113216]{0:T(1024)} fusion(u32[7113216]{0} %x)"
    ) == "fusion.76 u32[7113216]"
    assert tracing.op_name("%while.6 = (u32[], s32[3,4]{1,0}) while(%t)") \
        == "while.6"
    assert tracing.op_name("copy.1") == "copy.1"


def test_host_spans_read_from_an_xplane(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    spans = tracing.Spans()
    jax.profiler.start_trace(str(tmp_path))
    with spans.span("window"):
        with spans.span("draw"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = tracing.events_from_xplane(str(tmp_path))
    names = sorted(n for n, _, _ in ev["host"])
    assert names == ["draw", "window"]
    tr = Trace.from_events(ev)
    (ws, we), = tr.spans("window")
    (ds, de), = tr.spans("draw")
    assert ws <= ds and de <= we
