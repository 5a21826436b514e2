"""Sample-size estimation and capacity planning.

XLA needs static output shapes, so samplers draw into a fixed-capacity
buffer. The expected Poisson sample size and its variance are exactly
computable from the index in O(|N|):
    E[k] = sum_t w_t * p_t,     Var[k] = sum_t w_t * p_t * (1 - p_t)
(independent Bernoulli trials). Capacity = E + sigmas * sqrt(Var) + slack
covers overflow with probability ~1 - 1e-9 at sigmas=6; poisson.py re-draws
with doubled capacity on the (measurable) overflow event.

These are host-side numpy reductions over the last axis (plans call them
once per bind, on a host copy of the plan-bound (w, p); a stacked (S, R)
input gives per-shard values). Plans never ask the device for them: the
TPU compiler emulates float64 and takes minutes over such reductions.
"""
from __future__ import annotations

import math

import numpy as np

__all__ = ["expected_sample_size", "sample_std", "plan_capacity", "round_up"]


def _f64(x) -> np.ndarray:
    return np.asarray(x, dtype=np.float64)


def expected_sample_size(w, p) -> np.ndarray:
    return np.sum(_f64(w) * _f64(p), axis=-1)


def sample_std(w, p) -> np.ndarray:
    p = _f64(p)
    return np.sqrt(np.sum(_f64(w) * p * (1.0 - p), axis=-1))


def exprace_arrival_mass(w, p) -> np.ndarray:
    """Expected raw Poisson-arrival count of the EXPRACE sampler:
    Lam = sum_t w_t * (-ln(1 - min(p_t, 1-p_t))), always <= ln2 * sum w_t/2."""
    p = np.clip(_f64(p), 0.0, 1.0)
    pi = np.minimum(p, 1.0 - p)
    return np.sum(_f64(w) * (-np.log1p(-np.minimum(pi, 0.5))), axis=-1)


def round_up(x: int, multiple: int = 128) -> int:
    return int(-(-x // multiple)) * multiple


def plan_capacity(mean: float, std: float, sigmas: float = 6.0, slack: int = 64,
                  multiple: int = 128) -> int:
    """Static capacity for a sampler invocation (multiple of 128 for TPU lanes)."""
    cap = int(math.ceil(float(mean) + sigmas * float(std))) + slack
    return round_up(max(cap, multiple), multiple)
