"""Plain numpy reference for Poisson sampling over a tree of equi-joins.

Independent of the system under test: it reads only the generated numpy
columns and the configuration's join tree, and imports nothing of the
program. A tree is a list of nodes in parent-before-child order::

    {"relation": "Cast", "vars": ["t", "person"], "parent": "Title", "on": "t"}

The root (no ``parent``) carries the probability variable. Every child
joins its parent on one variable ``on``; key values are non-negative
integers. A join tuple is one row of every relation agreeing on the join
variables, and a Poisson sample keeps each join tuple independently with
the root row's probability.

What it answers:

* ``row_hashes``/``member``: a tuple is in the join iff, for every
  relation, its projection onto that relation's variables is a row of it
  (compared by a 64-bit hash of the exact bits of every value, so a
  probability rounded to float32 is not a member);
* ``mean``/``std``: the sample size's expectation and standard deviation,
  ``sum(w*p)`` and ``sqrt(sum(w*p*(1-p)))`` over root rows of join weight
  ``w``; ``psum_mean``/``psum_std``: the same for the sum of ``p`` over
  the sampled tuples, ``sum(w*p^2)`` and ``sqrt(sum(w*p^3*(1-p)))``;
* ``bag_checksum``: an order-independent checksum of the full join bag;
* ``sample``: a Poisson sample drawn by the reference itself in a given
  float precision (the control that must fail the comparison).
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

__all__ = ["Reference", "mix", "value_bits", "tuple_hashes", "checksum",
           "rounded"]

_M1 = np.uint64(0xBF58476D1CE4E5B9)
_M2 = np.uint64(0x94D049BB133111EB)
_GOLDEN = np.uint64(0x9E3779B97F4A7C15)


def mix(x: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer over a uint64 array (wrapping arithmetic)."""
    with np.errstate(over="ignore"):
        x = x ^ (x >> np.uint64(30))
        x = x * _M1
        x = x ^ (x >> np.uint64(27))
        x = x * _M2
        return x ^ (x >> np.uint64(31))


def value_bits(col: np.ndarray) -> np.ndarray:
    """The exact bits of a column as uint64: float64 by its IEEE bits,
    integers by their int64 value. A float32 column is widened to float64
    first, so a value rounded to float32 keeps its rounded bits."""
    col = np.ascontiguousarray(col)
    if col.dtype.kind == "f":
        return col.astype(np.float64).view(np.uint64)
    return col.astype(np.int64).view(np.uint64)


def rounded(x: np.ndarray, precision: str) -> np.ndarray:
    """``x`` rounded to the nearest value of ``precision`` (``float64``,
    ``float32`` or ``bfloat16``, by way of float32), held in float64,
    which holds each of them exactly."""
    x = np.asarray(x, np.float64)
    if precision == "float64":
        return x
    if precision not in ("float32", "bfloat16"):
        raise ValueError(f"unknown precision {precision!r}")
    f = np.ascontiguousarray(x.astype(np.float32))
    if precision == "bfloat16":  # keep the top 16 bits, ties to even
        b = f.view(np.uint32)
        b = (b + np.uint32(0x7FFF) + ((b >> np.uint32(16)) & np.uint32(1))) \
            & np.uint32(0xFFFF0000)
        f = b.view(np.float32)
    return f.astype(np.float64)


def _row_hash(cols: Sequence[np.ndarray], n: int) -> np.ndarray:
    h = np.zeros(n, np.uint64)
    for c in cols:
        h = mix(h ^ value_bits(c) ^ _GOLDEN)
    return h


def tuple_hashes(part_hashes: Sequence[np.ndarray]) -> np.ndarray:
    """One hash per join tuple from its per-relation projection hashes,
    chained in tree order (not a sum, so swapped pairings differ)."""
    h = np.zeros(part_hashes[0].shape[0], np.uint64)
    for p in part_hashes:
        h = mix(h ^ p)
    return h


def checksum(h: np.ndarray) -> int:
    """Wrapping sum of per-tuple hashes: equal bags give equal sums."""
    return int(h.sum(dtype=np.uint64))


class Reference:
    """The join of ``tree`` over ``columns`` ({relation: {var: array}})."""

    def __init__(self, tree: List[dict], columns: Dict[str, Dict[str, np.ndarray]],
                 prob_var: str):
        self.tree = tree
        self.columns = columns
        self.prob_var = prob_var
        self.root = tree[0]
        if self.root.get("parent") is not None:
            raise ValueError("the first tree node must be the root")
        if prob_var not in self.root["vars"]:
            raise ValueError("the probability variable must be on the root")
        self.variables = []
        for node in tree:
            for v in node["vars"]:
                if v not in self.variables:
                    self.variables.append(v)
        self.n = {node["relation"]: self._rows(node) for node in tree}
        self.children = {node["relation"]: [] for node in tree}
        for node in tree[1:]:
            self.children[node["parent"]].append(node)
        self.weight = self._weights()
        w = self.weight[self.root["relation"]].astype(np.float64)
        p = np.asarray(columns[self.root["relation"]][prob_var], np.float64)
        self.size = int(self.weight[self.root["relation"]].sum())
        self.mean = float((w * p).sum())
        self.std = float(np.sqrt((w * p * (1.0 - p)).sum()))
        self.psum_mean = float((w * p * p).sum())
        self.psum_std = float(np.sqrt((w * p ** 3 * (1.0 - p)).sum()))
        self._hashes = {}

    def _rows(self, node) -> int:
        cols = self.columns[node["relation"]]
        return int(cols[node["vars"][0]].shape[0])

    def _key(self, node, var) -> np.ndarray:
        k = np.asarray(self.columns[node["relation"]][var])
        if k.shape[0] and k.min() < 0:
            raise ValueError(f"{node['relation']}.{var}: negative join key")
        return k.astype(np.int64)

    def _weights(self) -> Dict[str, np.ndarray]:
        """Join weight of every row: the number of join tuples it is in,
        counting only its subtree (bottom-up products of child sums)."""
        weight = {}
        for node in reversed(self.tree):
            rel = node["relation"]
            w = np.ones(self.n[rel], np.int64)
            for child in self.children[rel]:
                ck = self._key(child, child["on"])
                pk = self._key(node, child["on"])
                top = int(max(ck.max(initial=-1), pk.max(initial=-1))) + 1
                per_key = np.bincount(ck, weights=weight[child["relation"]],
                                      minlength=top)
                w = w * np.rint(per_key[pk]).astype(np.int64)
            weight[rel] = w
        return weight

    # -- membership -----------------------------------------------------------
    def row_hashes(self, node) -> np.ndarray:
        """Per-row hash of ``node``'s relation over its variables."""
        rel = node["relation"]
        if rel not in self._hashes:
            cols = self.columns[rel]
            self._hashes[rel] = _row_hash([cols[v] for v in node["vars"]],
                                          self.n[rel])
        return self._hashes[rel]

    def projection_hashes(self, out: Dict[str, np.ndarray]) -> List[np.ndarray]:
        """Per-relation projection hashes of tuples given as columns."""
        k = int(next(iter(out.values())).shape[0])
        return [_row_hash([out[v] for v in node["vars"]], k)
                for node in self.tree]

    def bad_rows(self, out: Dict[str, np.ndarray]) -> int:
        """Projections of the tuples of ``out`` that are no row of their
        relation, summed over the relations: 0 iff every tuple is in the
        join with every attribute exact."""
        return self.bad_parts(self.projection_hashes(out))

    def bad_parts(self, parts: List[np.ndarray]) -> int:
        """``bad_rows`` from the tuples' projection hashes."""
        bad = 0
        for node, h in zip(self.tree, parts):
            keys = self._sorted(node)
            if keys.shape[0] == 0:
                bad += h.shape[0]
                continue
            h = np.sort(h)  # sorted needles make the search sequential
            i = np.minimum(np.searchsorted(keys, h), keys.shape[0] - 1)
            bad += int((keys[i] != h).sum())
        return bad

    def _sorted(self, node) -> np.ndarray:
        name = ("sorted", node["relation"])
        if name not in self._hashes:
            self._hashes[name] = np.sort(self.row_hashes(node))
        return self._hashes[name]

    def out_checksum(self, out: Dict[str, np.ndarray]) -> int:
        """Bag checksum of tuples given as columns (program output)."""
        return checksum(tuple_hashes(self.projection_hashes(out)))

    # -- the full join --------------------------------------------------------
    def bag_rows(self) -> Dict[str, np.ndarray]:
        """Row indices, per relation, of every join tuple: the root rows
        expanded child by child (each tuple repeated once per matching
        child row)."""
        rel0 = self.root["relation"]
        rows = {rel0: np.arange(self.n[rel0], dtype=np.int64)}
        for node in self.tree[1:]:
            rel, parent = node["relation"], node["parent"]
            ck = self._key(node, node["on"])
            pk = self._key(self._node(parent), node["on"])[rows[parent]]
            top = int(max(ck.max(initial=-1), pk.max(initial=-1))) + 1
            cnt = np.bincount(ck, minlength=top)
            order = np.argsort(ck, kind="stable")
            start = np.concatenate([[0], np.cumsum(cnt)])[:-1]
            reps = cnt[pk]
            src = np.repeat(np.arange(pk.shape[0]), reps)
            base = np.repeat(np.cumsum(reps) - reps, reps)
            off = np.arange(src.shape[0], dtype=np.int64) - base
            child_rows = order[start[pk[src]] + off]
            rows = {r: v[src] for r, v in rows.items()}
            rows[rel] = child_rows
        return rows

    def _node(self, rel: str):
        return next(n for n in self.tree if n["relation"] == rel)

    def bag_checksum(self) -> int:
        """Checksum of the reference's full join bag."""
        rows = self.bag_rows()
        parts = [self.row_hashes(node)[rows[node["relation"]]]
                 for node in self.tree]
        return checksum(tuple_hashes(parts))

    def bag_columns(self, rows=None) -> Dict[str, np.ndarray]:
        """The full join (or the tuples at ``rows``) as variable columns."""
        rows = self.bag_rows() if rows is None else rows
        out = {}
        for node in self.tree:
            cols = self.columns[node["relation"]]
            for v in node["vars"]:
                if v not in out:
                    out[v] = np.asarray(cols[v])[rows[node["relation"]]]
        return out

    # -- the control ----------------------------------------------------------
    def sample(self, rng: np.random.Generator, precision: str = "float64",
               rows=None) -> Dict[str, np.ndarray]:
        """One Poisson sample drawn by the reference, with the inclusion
        test and the returned probability column rounded to ``precision``
        (see ``rounded``; ``rows``: the bag's row indices, when already
        computed)."""
        rows = self.bag_rows() if rows is None else rows
        rel0 = self.root["relation"]
        p = rounded(self.columns[rel0][self.prob_var], precision)
        keep = rounded(rng.random(rows[rel0].shape[0]), precision) \
            < p[rows[rel0]]
        out = self.bag_columns({r: v[keep] for r, v in rows.items()})
        out[self.prob_var] = p[rows[rel0][keep]]
        return out
