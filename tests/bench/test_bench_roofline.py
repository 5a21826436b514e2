"""Bytes a draw or a full join must move, against hand counts."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import json
import os

import numpy as np
import pytest

from bench.metrics import roofline_bytes as rb

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _cfg(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def _tables(tree, n_root):
    """Tables of int64 keys and attributes with a float64 probability."""
    out = {}
    for i, node in enumerate(tree):
        n = n_root if i == 0 else 7
        out[node["relation"]] = {
            v: (np.zeros(n, np.float64) if v == "p" else np.zeros(n, np.int64))
            for v in node["vars"]}
    return out


def test_job_star_hand_count():
    cfg = _cfg("job-star")
    tables = _tables(cfg["tree"], 1000)
    # rows: title (t, kind, p) 24 + cast_info (t, person) 16 +
    # movie_companies (t, comp) 16 = 56; output (t, kind, p, person, comp)
    # 40; position 8. Root pass: 1000 * (w 8 + p 8).
    assert rb.row_bytes(cfg["tree"], tables) == 56
    assert rb.out_bytes(cfg["tree"], tables) == 40
    assert rb.draw_bytes(cfg["tree"], tables, "p", 500) == 1000 * 16 + 500 * 104
    assert rb.flatten_bytes(cfg["tree"], tables, 500) == 500 * 96


def test_stats_ceb_hand_count():
    cfg = _cfg("stats-ceb")
    tables = _tables(cfg["tree"], 10)
    # rows: users (u, rep, p) 24 + posts (post, u) 16 + votes (post, vtype)
    # 16 = 56; output (u, rep, p, post, vtype) 40.
    assert rb.draw_bytes(cfg["tree"], tables, "p", 3) == 10 * 16 + 3 * 104
    assert rb.flatten_bytes(cfg["tree"], tables, 3) == 3 * 96


def test_share_is_a_percent_of_the_bandwidth_roofline():
    peaks = {"hbm_bytes_per_s": 800e9}
    # 8 GB in 20 ms is 400 GB/s: half the roofline
    assert rb.share(8e9, 0.02, peaks) == pytest.approx(50.0)
    with open(os.path.join(ROOT, "bench", "peaks.json")) as f:
        v5e = json.load(f)["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9 and "Google Cloud" in v5e["source"]
