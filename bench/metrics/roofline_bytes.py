"""Bytes a draw or a full join must move, from the cell's shapes alone.

Whatever implements it, an operation over the join must read the root's
weight and probability once (a draw), read for every output tuple one row
of each relation, and write the tuple (and, for a draw, its position).
The shares built on these counts are bandwidth shares: the operations
are gathers, so the bytes bound applies, not the FLOP bound.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

WEIGHT_BYTES = 8     # a root row's join weight, int64
POSITION_BYTES = 8   # a sampled tuple's position in the join, int64


def row_bytes(tree, tables: Dict[str, Dict[str, np.ndarray]]) -> int:
    """One row of every relation of the tree."""
    return sum(np.asarray(tables[n["relation"]][v]).dtype.itemsize
               for n in tree for v in n["vars"])


def out_bytes(tree, tables) -> int:
    """One output tuple: every variable once."""
    seen, total = set(), 0
    for n in tree:
        for v in n["vars"]:
            if v not in seen:
                seen.add(v)
                total += np.asarray(tables[n["relation"]][v]).dtype.itemsize
    return total


def draw_bytes(tree, tables, prob_var: str, tuples: float) -> float:
    """A Poisson draw returning ``tuples`` tuples."""
    root = tables[tree[0]["relation"]]
    n_root = np.asarray(root[prob_var]).shape[0]
    root_pass = n_root * (WEIGHT_BYTES + np.asarray(root[prob_var]).dtype.itemsize)
    return root_pass + tuples * (row_bytes(tree, tables) + out_bytes(tree, tables)
                                 + POSITION_BYTES)


def flatten_bytes(tree, tables, tuples: float) -> float:
    """A full join of ``tuples`` tuples."""
    return tuples * (row_bytes(tree, tables) + out_bytes(tree, tables))


def share(nbytes: float, seconds: float, peaks: dict) -> float:
    """Percent of the bandwidth roofline: least time over ``seconds``."""
    return 100.0 * (nbytes / peaks["hbm_bytes_per_s"]) / seconds
