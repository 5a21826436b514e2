"""Shared pieces of the configurations' data generators.

A configuration draws its tables' structure (sizes, fan-outs, skew and
attribute values) from a fixed ``structure_seed``, and the run's ``--seed``
only relabels the join keys: every seed then holds the same join, with
the same sizes and probabilities, in another order. So a run's work does
not depend on its seed; its draws do.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

__all__ = ["probabilities", "relabel"]


def probabilities(rng: np.random.Generator, spec: dict, n: int,
                  dtype: str = "float64") -> np.ndarray:
    """``n`` tuple probabilities from the configuration's distribution:
    ``{"beta": [a, b]}`` (the paper's low distribution is Beta(2, 10)),
    stored in ``dtype``, the precision the configuration states for them."""
    if set(spec) != {"beta"}:
        raise ValueError(f"unknown probability distribution {spec}")
    if dtype not in ("float64", "float32"):
        raise ValueError(f"probabilities are float64 or float32, not {dtype}")
    a, b = spec["beta"]
    return rng.beta(a, b, n).astype(dtype)


def relabel(tables: Dict[str, Dict[str, np.ndarray]],
            domains: Dict[str, Tuple[int, List[Tuple[str, str]]]],
            seed: int) -> None:
    """Relabel key domains in place: each domain ``(size, columns)`` is
    permuted by ``seed``, and every (relation, column) of it is mapped
    through the permutation."""
    rng = np.random.default_rng(seed)
    for name in sorted(domains):
        size, columns = domains[name]
        perm = rng.permutation(size).astype(np.int64)
        for rel, col in columns:
            tables[rel][col] = perm[tables[rel][col]]
