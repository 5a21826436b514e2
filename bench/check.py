"""The numbers that decide ``correct``, computed from what the window
produced against the plain reference, and their limits.

Each number is a reading of one guarantee of the configuration:

* ``bad_rows``: projections of checked tuples onto a relation that are no
  row of it, summed over relations: 0 iff every tuple is a tuple of the
  join with every attribute exact (limit 0: an exact comparison);
* ``count_z``: the largest |z| of an answer's sample size against the
  reference's sum(w*p) and standard deviation;
* ``psum_z``: the largest |z| of the sum of p over a checked answer's
  tuples against sum(w*p^2), which a sampler that keeps tuples at the wrong
  probabilities moves;
* ``repeated``: checked answers whose bag of tuples equals an earlier
  one's, which independent draws all but never do (limit 0);
* ``unanswered``: requests of the window never answered (limit 0);
* ``row_count_diff``/``bag_mismatch``: the full join's size against the
  reference's, and checked joins whose bag checksum differs (limit 0).

Limits live in ``bench/limits/<cell>.json``; PERF.md gives the readings
each was set from.
"""
from __future__ import annotations

from typing import Dict, Iterable, List

import numpy as np

from bench.reference import checksum, tuple_hashes

__all__ = ["sample_numbers", "join_numbers", "merged", "judge"]

# Numbers that are the largest over answers; the others count answers.
LARGEST = ("count_z", "psum_z", "row_count_diff")


def _z(x: float, mean: float, std: float) -> float:
    return abs(x - mean) / max(std, 1e-12)


def sample_numbers(ref, counts: Iterable[int], checked: List[Dict[str, np.ndarray]]
                   ) -> Dict[str, float]:
    """Numbers of a set of Poisson answers: ``counts`` of every answer
    that did not overflow, and the host columns (cut to their counts) of
    the checked ones."""
    counts = list(counts)
    parts = [ref.projection_hashes(c) for c in checked]
    out = {
        "bad_rows": float(sum(ref.bad_parts(p) for p in parts)),
        "count_z": max((_z(k, ref.mean, ref.std) for k in counts),
                       default=0.0),
        "psum_z": max((_z(float(np.sum(c[ref.prob_var], dtype=np.float64)),
                          ref.psum_mean, ref.psum_std) for c in checked),
                      default=0.0),
    }
    sums = [checksum(tuple_hashes(p)) for p in parts]
    out["repeated"] = float(len(sums) - len(set(sums)))
    return out


def join_numbers(ref, sizes: Iterable[int], checked: List[Dict[str, np.ndarray]]
                 ) -> Dict[str, float]:
    """Numbers of a set of full joins: every call's row count and the host
    columns of the checked ones."""
    want = ref.bag_checksum() if checked else None
    return {
        "row_count_diff": float(max((abs(n - ref.size) for n in sizes),
                                    default=0)),
        "bag_mismatch": float(sum(ref.out_checksum(c) != want
                                  for c in checked)),
    }


def merged(parts: List[Dict[str, float]]) -> Dict[str, float]:
    """The numbers of several query shapes as one: the largest |z| and
    size difference, the sum of the counts."""
    out: Dict[str, float] = {}
    for p in parts:
        for k, v in p.items():
            out[k] = max(out.get(k, v), v) if k in LARGEST else \
                out.get(k, 0.0) + v
    return out


def judge(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, {name: {"value", "limit"}}): every number at or under
    its limit. A number without a limit is an error, not a pass; a limit
    without a number (nothing was produced to compare) is not correct."""
    missing = sorted(set(numbers) - set(limits))
    if missing:
        raise KeyError(f"no limit for {missing}")
    table = {k: {"value": numbers.get(k), "limit": limits[k]} for k in limits}
    ok = all(k in numbers and np.isfinite(numbers[k])
             and numbers[k] <= limits[k] for k in limits)
    return ok, table
