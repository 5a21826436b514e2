"""Data of the ``job-star`` configuration: JOB's IMDB star around title."""
from __future__ import annotations

import numpy as np

from bench.datagen import probabilities, relabel


def generate(cfg: dict, seed: int) -> dict:
    """{relation: {column: numpy array}} for the run's ``seed``."""
    rng = np.random.default_rng(cfg["structure_seed"])
    n_t = cfg["rows"]["title"]
    n_c = cfg["rows"]["cast_info"]
    n_m = cfg["rows"]["movie_companies"]
    tables = {
        "title": {"t": np.arange(n_t, dtype=np.int64),
                  "kind": rng.integers(0, cfg["domains"]["kind"], n_t),
                  "p": probabilities(rng, cfg["p"], n_t,
                                     cfg["precision"][cfg["prob_var"]])},
        "cast_info": {"t": rng.integers(0, n_t, n_c),
                      "person": rng.integers(0, cfg["domains"]["person"],
                                             n_c)},
        "movie_companies": {"t": rng.integers(0, n_t, n_m),
                            "comp": rng.integers(0, cfg["domains"]["company"],
                                                 n_m)},
    }
    relabel(tables, {"t": (n_t, [("title", "t"), ("cast_info", "t"),
                                 ("movie_companies", "t")])}, seed)
    return tables
