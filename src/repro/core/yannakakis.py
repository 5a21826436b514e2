"""Full acyclic join processing and the Materialize-and-Scan baselines.

The same shredded index that backs Poisson sampling computes full joins
(flatten mu*) — the paper's "single engine basis, no regret" point (§6.3).

Baselines (paper §6 "Baseline"):
  M-CSYA / M-USYA : build the CSR/USR index, flatten, per-tuple Bernoulli.
  M-BJ            : pairwise materializing joins (sort-merge here — XLA has
                    no hash tables; retains the defining property of
                    materializing every intermediate), then Bernoulli scan.
"""
from __future__ import annotations

import warnings
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import probe
from .database import Database
from .jointree import JoinQuery, JoinTreeNode, gyo_join_tree
from .relations import Relation, dense_keys, numeric_column, take_column
from .shred import Shred, build_shred

__all__ = ["flatten", "full_join", "materialize_and_scan", "binary_join"]

I64 = jnp.int64


def flatten(shred: Shred, rep: Optional[str] = None) -> Dict[str, jnp.ndarray]:
    """mu*(N): materialize the full join result from the index by probing
    every position. (The paper's sequential flatten is an O(n) pointer walk;
    the bulk-probe flatten is the order-identical data-parallel analogue.)"""
    n = int(shred.join_size)
    if n == 0 or shred.root.num_rows == 0:
        return {v: node.data.column(v)[:0]
                for node in shred.root.nodes() for v in node.owned}
    pos = jnp.arange(n, dtype=I64)
    return probe.get(shred, pos, rep=rep)


def full_join(db: Database, query: JoinQuery, rep: str = "usr") -> Dict[str, jnp.ndarray]:
    """Yannakakis via shredded semijoins + flatten (SYA; Prop 4.4/4.5).

    .. deprecated::
        Facade over ``repro.engine.QueryEngine.full_join`` (one throwaway
        engine — the shred index is rebuilt every call). Hold a
        ``QueryEngine`` instead so the index is cached across calls
        (DESIGN.md §7, §13)."""
    from repro.engine import QueryEngine  # lazy: engine imports repro.core

    warnings.warn(
        "core.yannakakis.full_join is deprecated; use "
        "repro.engine.QueryEngine.full_join — it caches the shred index "
        "across calls instead of rebuilding it per query",
        DeprecationWarning, stacklevel=2)
    return QueryEngine(db, rep=rep).full_join(query)


def materialize_and_scan(
    key,
    db: Database,
    query: JoinQuery,
    uniform_p: Optional[float] = None,
    rep: str = "usr",
) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray]:
    """The naive M&S algorithm: materialize |Q^(db)| tuples, Bernoulli each.

    Returns (full join columns, keep mask); the sample is cols[mask]. Kept
    un-compacted so callers can compare against I&P samples exactly.
    """
    shred = build_shred(db, query, rep=rep)
    cols = flatten(shred, rep="usr" if rep == "both" else rep)
    n = int(shred.join_size)
    if uniform_p is not None:
        pflat = jnp.full((n,), uniform_p, jnp.float64)
    else:
        assert query.prob_var is not None
        pflat = numeric_column(cols[query.prob_var]).astype(jnp.float64)
    keep = jax.random.uniform(key, (max(n, 1),), jnp.float64)[:n] < pflat
    return cols, keep


# ---------------------------------------------------------------------------
# M-BJ: pairwise materializing binary joins
# ---------------------------------------------------------------------------

def _pairwise_join(left: Relation, right: Relation) -> Relation:
    """Materializing sort-merge equi-join on the shared variables.

    Executed eagerly (output cardinality is data-dependent) — exactly why the
    paper replaces this plan shape with the index.
    """
    shared = sorted(set(left.attrs) & set(right.attrs))
    m, n = left.num_rows, right.num_rows
    if shared:
        kl, kr = dense_keys([left.column(v) for v in shared],
                            [right.column(v) for v in shared])
    else:
        kl, kr = jnp.zeros((m,), I64), jnp.zeros((n,), I64)
    order = jnp.argsort(kr, stable=True)
    kr_sorted = kr[order]
    s = jnp.searchsorted(kr_sorted, kl, side="left")
    e = jnp.searchsorted(kr_sorted, kl, side="right")
    counts = np.asarray(e - s)
    total = int(counts.sum())
    # Expand: output row t pairs left row lrow[t] with the (t - base)-th
    # element of its run in the sorted right side.
    lrow = np.repeat(np.arange(m), counts)
    base = np.repeat(np.cumsum(counts) - counts, counts)
    offs = np.arange(total) - base
    rpos = np.asarray(s)[lrow] + offs
    rrow = np.asarray(order)[rpos] if total else np.zeros((0,), np.int64)
    out = {v: take_column(left.column(v), jnp.asarray(lrow))
           for v in left.attrs}
    for v in right.attrs:
        if v not in out:
            out[v] = take_column(right.column(v), jnp.asarray(rrow))
    return Relation(out)


def binary_join(db: Database, query: JoinQuery) -> Dict[str, jnp.ndarray]:
    """M-BJ plan: join along the join tree bottom-up, materializing every
    intermediate (join order = post-order of the GYO tree)."""
    tree = gyo_join_tree(query)

    def rec(node: JoinTreeNode) -> Relation:
        rel = db.instance_for(node.atom)
        for c in node.children:
            rel = _pairwise_join(rel, rec(c))
        return rel

    return dict(rec(tree).columns)
