"""BENCHMARK.json and the files the harness finds by name: every cell's
configuration, generator, traffic mix and limits exist, every per-layer
metric has its reader, and names, units and lengths are in bounds."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import json
import re

import pytest

from bench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    assert all(os.path.isdir(os.path.join(ROOT, p)) for p in bench["paths"])
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_configs(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("bench/configs/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert {"source", "assumed", "reduced", "precision",
                "guarantees"} <= set(cfg)
        assert os.path.isfile(os.path.join(ROOT, c["file"][:-5] + ".py"))


def test_workloads(bench):
    configs = {c["name"] for c in bench["configs"]}
    used = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line(w["why"])
        used.add(w["config"])
        for path in (f"bench/traffic/{w['traffic']}.json",
                     f"bench/limits/{w['name']}.json"):
            assert os.path.isfile(os.path.join(ROOT, path)), path
    assert used == configs
    assert len({(w["config"], w["traffic"]) for w in bench["workloads"]}) \
        == len(bench["workloads"])


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES and _line(m["layer"])
        assert m["moves"] in e2e
        assert os.path.isfile(harness.metric_file(m["name"]))
        for w in m.get("workloads", cells):
            assert w in cells
            moved = e2e[m["moves"]]
            assert w in moved.get("workloads", cells)
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for w in cells:  # every cell: setup_s, one more end-to-end, a per-layer
        mine = [m for m in bench["end_to_end"] if w in m.get("workloads", cells)]
        assert len(mine) >= 2
        assert any(w in m.get("workloads", cells) for m in bench["per_layer"])


def test_check_time_fits_with_24_cells(bench):
    runs = 2 + 14 * 24
    total = runs * (bench["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_limits_cover_every_number():
    for name in os.listdir(os.path.join(ROOT, "bench", "limits")):
        with open(os.path.join(ROOT, "bench", "limits", name)) as f:
            limits = json.load(f)
        assert limits and all(v >= 0 for v in limits.values())


def test_cells_kept_for_later_are_complete():
    """Every cell that has limits resolves from its files by name, so that
    adding a cell kept out back is a matter of entries alone."""
    names = [f[:-len(".json")]
             for f in os.listdir(os.path.join(ROOT, "bench", "limits"))]
    assert {"job-star-p32.draw", "job-star.draw", "job-star.full_join",
            "stats-ceb.serve"} <= set(names)
    for w in names:
        cell = harness.resolve(w)
        assert cell.limits and cell.traffic["metric"] in {
            m["name"] for m in cell.end_to_end}
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
    with pytest.raises(KeyError):
        harness.resolve("no-such-config.draw")


def test_metric_reader_by_name_or_base():
    own = harness.metric_file("draw_device_s")
    assert own.endswith("draw_device_s.py")
    for cell in ("draw", "serve", "some_later_cell"):
        assert harness.metric_file(f"device_idle_share.{cell}").endswith(
            os.path.join("metrics", "device_idle_share.py"))
    assert harness.metric_file("batch_lanes_mean.serve").endswith(
        "batch_lanes_mean.serve.py")
    with pytest.raises(FileNotFoundError):
        harness.metric_file("no_such_metric.draw")
