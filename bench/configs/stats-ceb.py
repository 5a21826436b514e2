"""Data of the ``stats-ceb`` configuration: STATS-CEB's owner and vote
chain, users - posts - votes, with Zipf-skewed degrees."""
from __future__ import annotations

import numpy as np

from bench.datagen import probabilities, relabel


def generate(cfg: dict, seed: int) -> dict:
    """{relation: {column: numpy array}} for the run's ``seed``."""
    rng = np.random.default_rng(cfg["structure_seed"])
    n_u = cfg["rows"]["users"]
    n_p = cfg["rows"]["posts"]
    n_v = cfg["rows"]["votes"]
    z = cfg["zipf"]
    tables = {
        "users": {"u": np.arange(n_u, dtype=np.int64),
                  "rep": rng.integers(0, cfg["domains"]["reputation"], n_u),
                  "p": probabilities(rng, cfg["p"], n_u,
                                     cfg["precision"][cfg["prob_var"]])},
        "posts": {"post": np.arange(n_p, dtype=np.int64),
                  "u": rng.zipf(z["posts_per_user"], n_p) % n_u},
        "votes": {"post": rng.zipf(z["votes_per_post"], n_v) % n_p,
                  "vtype": rng.integers(0, cfg["domains"]["vote_type"], n_v)},
    }
    relabel(tables, {"u": (n_u, [("users", "u"), ("posts", "u")]),
                     "post": (n_p, [("posts", "post"), ("votes", "post")])},
            seed)
    return tables
