"""A whole run of each cell at a tiny size, past the look for a chip: the
sound program is judged correct, and the control (the reference one
precision below the configuration's, in the program's place) and every
planted fault the cell can have are judged not correct."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import json
import shutil
import subprocess
import time

import pytest

from bench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# job-star at 20,000 titles: E[k] is about 49,000, large enough that half a
# draw left out reads past the cell's count_z limit, as it does at full size
TINY = {"job-star": {"title": 20000, "cast_info": 286700, "movie_companies": 20640},
        "job-star-p32": {"title": 20000, "cast_info": 286700,
                         "movie_companies": 20640},
        "stats-ceb": {"users": 200, "posts": 460, "votes": 1640}}
SMALL_SERVE = {"max_batch": 4, "rate_per_s": 40.0}

CASES = [(cell, fault)
         for cell, faults in (
             ("job-star.draw", ("sound", "control", "altered", "half", "stale")),
             ("job-star-p32.draw", ("sound", "control", "altered", "half",
                                    "stale")),
             ("job-star.full_join", ("sound", "control", "altered", "half")),
             ("stats-ceb.serve", ("sound", "control", "altered", "half", "stale")))
         for fault in faults]


def _run(cell, fault, seed=123456789012):
    import jax

    config = cell.split(".")[0]
    return harness.run(
        cell, seed, 0.5, False, t_start=time.perf_counter(),
        rows=TINY[config], fault=fault,
        traffic=SMALL_SERVE if cell.endswith("serve") else None,
        require=lambda chips: jax.devices(), log=lambda *a, **k: None)


@pytest.mark.parametrize("cell,fault", CASES)
def test_correct_only_when_sound(cell, fault):
    line = _run(cell, fault)
    assert line["correct"] is (fault == "sound"), line["checks"]
    assert list(line)[-1] == "checks"
    want = harness.resolve(cell)
    assert set(line["metrics"]) == {m["name"] for m in want.end_to_end}
    assert line["attempted"] >= 1


@pytest.mark.parametrize("fault", ["sound", "half", "stale"])
def test_closed_loop_of_batches(fault):
    import jax

    line = harness.run(
        "job-star.draw", 77, 0.5, False, t_start=time.perf_counter(),
        rows=TINY["job-star"], fault=fault,
        traffic={"call": "sample_batch", "batch": 3},
        require=lambda chips: jax.devices(), log=lambda *a, **k: None)
    assert line["correct"] is (fault == "sound"), line["checks"]
    assert line["attempted"] % 3 == 0


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "job-star.draw", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_without_the_program_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "job-star.draw", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


MIX = [{"relations": ["users", "posts"], "weight": 1},
       {"relations": ["users", "posts", "votes"], "weight": 2}]


@pytest.mark.parametrize("fault", ["sound", "control", "altered", "half",
                                   "stale"])
def test_bursts_of_a_query_mix(fault):
    """An open loop of two query shapes in on/off bursts: the batcher's
    grouping by shape and every shape's answers are judged."""
    import jax

    line = harness.run(
        "stats-ceb.serve", 2**33 + 5, 0.5, False, t_start=time.perf_counter(),
        rows=TINY["stats-ceb"], fault=fault,
        traffic=dict(SMALL_SERVE, queries=MIX, arrivals="on_off",
                     period_s=0.25, on_share=0.2, check_requests=12),
        require=lambda chips: jax.devices(), log=lambda *a, **k: None)
    assert line["correct"] is (fault == "sound"), line["checks"]
    assert line["attempted"] == 20


@pytest.mark.parametrize("fault", ["sound", "half"])
def test_closed_loop_of_a_query_mix(fault):
    import jax

    line = harness.run(
        "job-star.draw", 78, 0.5, False, t_start=time.perf_counter(),
        rows=TINY["job-star"], fault=fault,
        traffic={"queries": [{"relations": ["title", "cast_info"]},
                             {"relations": ["title", "movie_companies"]}],
                 "check_calls": 4},
        require=lambda chips: jax.devices(), log=lambda *a, **k: None)
    assert line["correct"] is (fault == "sound"), line["checks"]


def test_a_query_shape_must_hold_the_root():
    cell = harness.resolve("job-star.draw")
    cell.traffic = dict(cell.traffic, queries=[{"relations": ["cast_info"]}])
    with pytest.raises(ValueError, match="subtree"):
        harness.build_session(cell, 1, TINY["job-star"])


def test_a_cold_set_up_is_marked():
    """Set-up counts the executables it compiled: a run whose draw has
    not been compiled yet is cold, a second run on the same program loads
    or compiles nothing."""
    import jax

    rows = {"title": 500, "cast_info": 7000, "movie_companies": 520}
    sess = harness.build_session(harness.resolve("job-star.draw"), 5, rows)
    kw = dict(require=lambda chips: jax.devices(), log=lambda *a, **k: None,
              sess=sess)
    first = harness.run("job-star.draw", 5, 0.2, False,
                        t_start=time.perf_counter(), **kw)
    again = harness.run("job-star.draw", 5, 0.2, False,
                        t_start=time.perf_counter(), **kw)
    assert first["setup"]["cold"] and first["setup"]["compiled"] > 0
    assert again["setup"] == {"compiled": 0, "loaded": 0, "cold": False}
    assert first["correct"] and again["correct"]
    assert list(again)[-2:] == ["setup", "checks"]
