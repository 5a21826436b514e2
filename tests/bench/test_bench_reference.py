"""The benchmark's plain reference against brute force on hand-made
joins, and against the engine at tiny sizes of both configurations."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import itertools

import numpy as np
import pytest

from bench import harness
from bench.reference import Reference, rounded

TINY = {"job-star": {"title": 300, "cast_info": 4300, "movie_companies": 310},
        "job-star-p32": {"title": 300, "cast_info": 4300,
                         "movie_companies": 310},
        "stats-ceb": {"users": 200, "posts": 460, "votes": 1640}}
CELL = {"job-star": "job-star.draw", "job-star-p32": "job-star-p32.draw",
        "stats-ceb": "stats-ceb.serve"}

CHAIN = [{"relation": "A", "vars": ["a", "x", "p"]},
         {"relation": "B", "vars": ["b", "a"], "parent": "A", "on": "a"},
         {"relation": "C", "vars": ["b", "y"], "parent": "B", "on": "b"},
         {"relation": "D", "vars": ["a", "z"], "parent": "A", "on": "a"}]


def _hand_tables(rng):
    return {
        "A": {"a": np.arange(4), "x": rng.integers(0, 3, 4),
              "p": rng.random(4)},
        "B": {"b": np.arange(7), "a": rng.integers(0, 5, 7)},
        "C": {"b": rng.integers(0, 8, 9), "y": rng.integers(0, 2, 9)},
        "D": {"a": rng.integers(0, 4, 5), "z": rng.integers(0, 9, 5)},
    }


def _brute(tables):
    """Every combination of one row per relation agreeing on shared vars."""
    rows = []
    names = [n["relation"] for n in CHAIN]
    sizes = [len(tables[r][next(iter(tables[r]))]) for r in names]
    for pick in itertools.product(*[range(s) for s in sizes]):
        t = {}
        ok = True
        for node, i in zip(CHAIN, pick):
            for v in node["vars"]:
                val = tables[node["relation"]][v][i]
                if v in t and t[v] != val:
                    ok = False
                t[v] = val
        if ok:
            rows.append(t)
    return rows


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reference_join_equals_brute_force(seed):
    tables = _hand_tables(np.random.default_rng(seed))
    ref = Reference(CHAIN, tables, "p")
    want = _brute(tables)
    assert ref.size == len(want)
    got = ref.bag_columns()
    assert sorted(tuple(int(t[v]) for v in "abxyz") for t in want) == \
        sorted(zip(*[got[v].astype(int).tolist() for v in "abxyz"]))
    assert ref.mean == pytest.approx(sum(t["p"] for t in want))
    cols = {v: np.array([t[v] for t in want]) for v in ref.variables}
    assert ref.bad_rows(cols) == 0
    assert ref.out_checksum(cols) == ref.bag_checksum()
    if want:
        cols["z"] = cols["z"] + 100  # no D row has such a z
        assert ref.bad_rows(cols) == len(want)


def test_float32_sample_is_not_in_the_join():
    tables = _hand_tables(np.random.default_rng(5))
    tables["A"]["p"] = np.full(4, 0.3)  # 0.3 is not a float32 value
    ref = Reference(CHAIN, tables, "p")
    rng = np.random.default_rng(0)
    exact = ref.sample(rng)
    assert ref.bad_rows(exact) == 0
    low = ref.sample(rng, "float32")
    k = len(low["p"])
    assert k > 0 and ref.bad_rows(low) == k


def test_bfloat16_sample_is_not_in_a_float32_join():
    """The control of a configuration that states float32: every tuple of
    a sample drawn in bfloat16 carries a p that is no row's."""
    tables = _hand_tables(np.random.default_rng(5))
    tables["A"]["p"] = np.full(4, 0.3, np.float32)
    ref = Reference(CHAIN, tables, "p")
    rng = np.random.default_rng(0)
    assert ref.bad_rows(ref.sample(rng, "float32")) == 0
    low = ref.sample(rng, "bfloat16")
    k = len(low["p"])
    assert k > 0 and ref.bad_rows(low) == k


def test_rounding_matches_the_machine_types():
    import ml_dtypes

    rng = np.random.default_rng(11)
    x = np.concatenate([rng.beta(2, 10, 10000), rng.random(10000),
                        [0.0, 1.0, 1 + 2.0 ** -8, 1 + 3 * 2.0 ** -8]])
    f32 = x.astype(np.float32)
    assert np.array_equal(rounded(x, "float64"), x)
    assert np.array_equal(rounded(x, "float32"), f32.astype(np.float64))
    assert np.array_equal(rounded(x, "bfloat16"),
                          f32.astype(ml_dtypes.bfloat16).astype(np.float64))
    assert rounded(np.array([1 + 2.0 ** -8]), "bfloat16")[0] == 1.0  # tie, even
    with pytest.raises(ValueError):
        rounded(x, "float16")


@pytest.mark.parametrize("config", ["job-star", "job-star-p32", "stats-ceb"])
def test_reference_agrees_with_the_engine(config):
    import jax

    cell = harness.resolve(CELL[config])
    sess = harness.build_session(cell, 2**31 + 7, TINY[config])
    ref = sess.reference()
    assert ref.size == sess.engine.join_size(sess.query)
    full = {v: np.asarray(c) for v, c in
            sess.engine.full_join(sess.query).items()}
    assert len(full["p"]) == ref.size
    assert ref.bad_rows(full) == 0
    assert ref.out_checksum(full) == ref.bag_checksum()
    for i in range(3):
        s = sess.engine.poisson_sample(sess.query, jax.random.key(i))
        k = int(s.count)
        out = {v: np.asarray(c)[:k] for v, c in s.columns.items()}
        assert ref.bad_rows(out) == 0
        assert abs(k - ref.mean) <= 6 * ref.std
    assert full["p"].dtype == sess.config["precision"]["p"]


def test_seed_relabels_but_keeps_the_work():
    cell = harness.resolve("stats-ceb.serve")
    cfg = dict(cell.config, rows=TINY["stats-ceb"])
    a = cell.generator.generate(cfg, 1)
    b = cell.generator.generate(cfg, 2)
    ra = Reference(cfg["tree"], a, "p")
    rb = Reference(cfg["tree"], b, "p")
    assert not np.array_equal(a["votes"]["post"], b["votes"]["post"])
    assert ra.size == rb.size and ra.mean == pytest.approx(rb.mean)
    assert ra.bag_checksum() != rb.bag_checksum()


def test_a_self_join_binds_aliases_to_one_table():
    """Nodes with a ``table`` key are aliases of one generated table (a
    self join, as EpiQL's contact query): their variables bind to its
    columns in order, and the reference agrees with the engine."""
    import types

    import jax

    def generate(cfg, seed):
        rng = np.random.default_rng(seed)
        n = cfg["rows"]["person"]
        return {"contact": {"x": rng.integers(0, n, 300),
                            "y": rng.integers(0, n, 300),
                            "p": rng.random(300)},
                "person": {"id": rng.integers(0, n, n + 20),
                           "age": rng.integers(0, 90, n + 20)}}

    tree = [{"relation": "contact", "vars": ["a", "b", "p"]},
            {"relation": "pa", "table": "person", "vars": ["a", "age_a"],
             "parent": "contact", "on": "a"},
            {"relation": "pb", "table": "person", "vars": ["b", "age_b"],
             "parent": "contact", "on": "b"}]
    cell = harness.Cell("epi.draw", 1, {"tree": tree, "prob_var": "p",
                                        "rows": {"person": 40}},
                        types.SimpleNamespace(generate=generate), {}, {},
                        [], [])
    sess = harness.build_session(cell, 3)
    assert set(sess.engine.db.relations) == {"contact", "person"}
    assert np.array_equal(sess.tables["pa"]["age_a"],
                          sess.tables["pb"]["age_b"])
    ref = sess.reference()
    assert ref.size == sess.engine.join_size(sess.query) > 300
    full = {v: np.asarray(c) for v, c in
            sess.engine.full_join(sess.query).items()}
    assert ref.bad_rows(full) == 0
    assert ref.out_checksum(full) == ref.bag_checksum()
    s = sess.engine.poisson_sample(sess.query, jax.random.key(1))
    k = int(s.count)
    assert ref.bad_rows({v: np.asarray(c)[:k] for v, c in
                         s.columns.items()}) == 0
