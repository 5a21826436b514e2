"""The ``job-star.batch`` cell and the readers of its own metrics: the
roofline share of a call of ``batch`` draws, and the device time of the
gather of float64 columns as bit patterns (``usr_gather_bits``)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import types

import numpy as np
import pytest

from bench import harness, scopes, tracing
from bench.metrics import roofline_bytes as rb


def _reader(name):
    return harness.load_module(harness.metric_file(name))


def _trace(ops):
    """Two ``draw`` spans (0..40, 50..90) over the given device ops."""
    return tracing.Trace.from_events(
        {"device": {"/device:TPU:0": [list(o) for o in ops]},
         "host": [["window", 0, 100], ["draw", 0, 40], ["draw", 50, 40]]})


def test_the_cell_draws_four_keys_a_call_with_p_in_float64():
    cell = harness.resolve("job-star.batch")
    assert cell.traffic["call"] == "sample_batch" and cell.traffic["batch"] == 4
    assert cell.traffic["check_calls"] == 1 and cell.traffic["span"] == "draw"
    assert cell.config["precision"]["p"] == "float64" and cell.chips == 1
    names = {m["name"] for m in cell.per_layer}
    assert {"device_idle_share.batch", "batch_roofline_share",
            "draw_gather_bits_device_s", "draw_device_s",
            "draw_gather_device_s"} <= names
    assert "draw_roofline_share" not in names
    assert {m["name"] for m in cell.end_to_end} == {"draw_s", "setup_s"}


@pytest.mark.parametrize("batch", [None, 1, 4])
def test_batch_roofline_share_counts_every_draw_of_a_call(batch):
    cell = harness.resolve("job-star.batch")
    traffic = dict(cell.traffic, batch=batch)
    if batch is None:
        del traffic["batch"]
    tables = {n["relation"]: {v: np.zeros(10 if i == 0 else 7,
                                          np.float64 if v == "p" else np.int64)
                              for v in n["vars"]}
              for i, n in enumerate(cell.config["tree"])}
    ctx = types.SimpleNamespace(
        cell=types.SimpleNamespace(traffic=traffic, config=cell.config),
        trace=_trace([["fusion.1 u32[8]", 0, 20], ["fusion.2 u32[8]", 50, 30]]),
        counters={"mean_count": 500.0}, peaks={"hbm_bytes_per_s": 800e9},
        tables=tables)
    got = _reader("batch_roofline_share").read(ctx)
    if batch is None:
        assert got is None
        return
    # one call: 25 ns of device time a draw span
    want = rb.share(batch * (10 * 16 + 500 * 104), 25e-9, ctx.peaks)
    assert got == pytest.approx(want)


def test_gather_bits_reads_its_scope_and_nothing_without_it(monkeypatch):
    ops = [["fusion.1 u32[8]", 0, 10], ["fusion.2 u32[8]", 10, 6],
           ["fusion.3 u32[8]", 50, 10], ["fusion.4 u32[8]", 60, 4]]
    bits = "jit(f)/vmap(usr_gather)/usr_gather_bits/gather"
    names = {"fusion.1 u32[8]": "jit(f)/usr_gather/gather",
             "fusion.2 u32[8]": bits,
             "fusion.3 u32[8]": "jit(f)/usr_gather/gather",
             "fusion.4 u32[8]": bits}
    ctx = types.SimpleNamespace(trace=_trace(ops))
    monkeypatch.setattr(scopes, "live_op_names", lambda: {"jit_f": names})
    assert _reader("draw_gather_bits_device_s").read(ctx) == \
        pytest.approx(5e-9)
    assert _reader("draw_gather_device_s").read(ctx) == pytest.approx(15e-9)
    # a float32 cell: the gather opens no bit-pattern scope
    plain = {k: "jit(f)/usr_gather/gather" for k in names}
    monkeypatch.setattr(scopes, "live_op_names", lambda: {"jit_f": plain})
    assert _reader("draw_gather_bits_device_s").read(ctx) is None
    assert _reader("draw_gather_device_s").read(ctx) == pytest.approx(15e-9)
