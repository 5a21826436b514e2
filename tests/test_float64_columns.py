"""Float64 attributes come back exact on every path (DESIGN.md §9).

The device holds a float64 column as its IEEE-754 bit patterns in int64
(``relations.Float64Bits``); every entry point returns columns whose
``np.asarray`` is the stored float64, bit for bit. The values below are
ones a float32 pair cannot hold (``1 - 2**-53``, ``0.1``, subnormals) or
whose sign only the bits keep (``-0.0``). Every check compares bit
patterns with a numpy join that imports nothing of the engine.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Atom, Database, DeltaBatch, JoinQuery, Relation
from repro.core.distributed import partition_root
from repro.core.relations import Float64Bits, place_column
from repro.engine import QueryEngine
from repro.launch.fleet.batcher import JoinSampleRequest, MicroBatcher

NEAR_ONE = 1 - 2.0 ** -53
SPECIAL = np.array([NEAR_ONE, 0.1, 5e-324, 2.2250738585072014e-308 / 3,
                    -0.0, 1 / 3, np.pi, 1e300, -2.0 ** -1074])
PATHS = ["poisson_sample", "sample_batch", "full_join", "batcher_rows",
         "after_delta", "sharded_sample"]


def _tables(seed: int = 3):
    """R(t, p, a) at the root, S(t, b) below it. The first rows of R keep
    p = 1 - 2**-53 and carry the special values in ``a``; the S rows that
    join them carry them in ``b``: a draw holds them all but surely."""
    rng = np.random.default_rng(seed)
    n_r, n_s, k = 60, 240, len(SPECIAL)
    p = rng.beta(2, 10, n_r)
    p[:k] = NEAR_ONE
    p[k] = 0.1
    a = rng.standard_normal(n_r)
    a[:k] = SPECIAL
    t_s = rng.integers(0, n_r, n_s)
    t_s[:k] = np.arange(k)
    b = rng.standard_normal(n_s) * 1e-3
    b[:k] = SPECIAL
    return {"R": {"t": np.arange(n_r), "p": p, "a": a},
            "S": {"t": t_s, "b": b}}


QUERY = JoinQuery((Atom.of("R", "t", "p", "a"), Atom.of("S", "t", "b")),
                  prob_var="p")
VARS = ("t", "p", "a", "b")


def _bits(x) -> np.ndarray:
    x = np.asarray(x)
    return x.view(np.int64) if x.dtype == np.float64 else x.astype(np.int64)


def _join_bag(tables):
    """Sorted (t, p, a, b) bit-pattern tuples of the join, in numpy."""
    r, s = tables["R"], tables["S"]
    t = np.asarray(s["t"])
    rows = np.stack([t, _bits(r["p"])[t], _bits(r["a"])[t], _bits(s["b"])], 1)
    return sorted(map(tuple, rows.tolist()))


def _tuples(cols, k=None):
    k = len(np.asarray(cols["t"])) if k is None else k
    return [tuple(r) for r in np.stack(
        [_bits(np.asarray(cols[v])[:k]) for v in VARS], 1).tolist()]


def _draws(path, engine, tables):
    """The answers of ``path`` as lists of bit-pattern tuples, and the
    tables they must be answers over."""
    keys = jax.random.split(jax.random.key(17), 3)
    if path == "poisson_sample":
        s = engine.poisson_sample(QUERY, keys[0])
        return [_tuples(s.columns, int(s.count))], tables
    if path == "sample_batch":
        s = engine.sample_batch(QUERY, keys)
        return [_tuples({v: c[b] for v, c in s.columns.items()},
                        int(s.count[b])) for b in range(3)], tables
    if path == "full_join":
        return [_tuples(engine.full_join(QUERY))], tables
    if path == "batcher_rows":
        mb = MicroBatcher(engine, max_batch=4, max_wait_ms=1e9,
                          collect_rows=True)
        for seed in (5, 6, 7):
            mb.submit(JoinSampleRequest(QUERY, seed=seed))
        return [_tuples(r.rows) for r in mb.flush()], tables
    if path == "after_delta":
        extra = np.array([0.7, 5e-324, -0.0, 0.1])
        engine.poisson_sample(QUERY, keys[1])  # warm index, upgraded below
        engine.apply_delta(DeltaBatch.of(
            R={"insert": {"t": [60, 61], "p": [NEAR_ONE, 0.1],
                          "a": extra[:2]}},
            S={"insert": {"t": [60, 60, 61, 0], "b": extra}}))
        new = {"R": {c: np.concatenate([tables["R"][c], ins])
                     for c, ins in (("t", [60, 61]), ("p", [NEAR_ONE, 0.1]),
                                    ("a", extra[:2]))},
               "S": {"t": np.concatenate([tables["S"]["t"], [60, 60, 61, 0]]),
                     "b": np.concatenate([tables["S"]["b"], extra])}}
        s = engine.poisson_sample(QUERY, keys[2])
        got = _tuples(s.columns, int(s.count))
        assert any(t[0] == 60 for t in got)  # the inserted rows are drawn
        return [got], new
    if path == "sharded_sample":
        mesh = jax.make_mesh((1,), ("data",))
        s = engine.poisson_sample(QUERY, keys[0], mesh=mesh, axes=("data",))
        return [_tuples(s.columns, int(s.count))], tables
    raise ValueError(path)


@pytest.mark.parametrize("path", PATHS)
def test_float64_attributes_come_back_exact(path):
    tables = _tables()
    engine = QueryEngine(Database.from_columns(tables))
    answers, tables = _draws(path, engine, tables)
    bag = _join_bag(tables)
    allowed = set(bag)
    special = set(_bits(SPECIAL).tolist())
    for got in answers:
        assert got and set(got) <= allowed
        assert special <= {t[2] for t in got}
        assert special <= {t[3] for t in got}
    if path == "full_join":
        assert sorted(answers[0]) == bag


def test_batch_lanes_equal_single_draws_bit_for_bit():
    engine = QueryEngine(Database.from_columns(_tables(4)))
    keys = jax.random.split(jax.random.key(9), 4)
    batch = engine.sample_batch(QUERY, keys)
    for b in range(4):
        one = engine.poisson_sample(QUERY, keys[b])
        assert int(batch.count[b]) == int(one.count)
        for v in one.columns:
            assert np.array_equal(_bits(np.asarray(batch.columns[v])[b]),
                                  _bits(one.columns[v]))


def test_no_device_leaf_of_a_float64_attribute_is_float64():
    engine = QueryEngine(Database.from_columns(_tables()))
    db = engine.db
    assert all(isinstance(db.relations[r].column(c), Float64Bits)
               for r, c in (("R", "p"), ("R", "a"), ("S", "b")))
    shred = engine.compile(QUERY).shred
    data = [n.data for n in shred.root.nodes()]
    keys = jax.random.split(jax.random.key(1), 2)
    outs = [data, engine.poisson_sample(QUERY, keys[0]).columns,
            engine.sample_batch(QUERY, keys).columns,
            engine.full_join(QUERY)]
    dtypes = {leaf.dtype for leaf in jax.tree.leaves(outs)}
    assert jnp.float64 not in dtypes and np.dtype(np.int64) in dtypes


def test_float32_and_integer_columns_keep_their_dtypes():
    rng = np.random.default_rng(0)
    db = Database.from_columns({
        "R": {"t": np.arange(40, dtype=np.int32),
              "p": rng.random(40).astype(np.float32)},
        "S": {"t": rng.integers(0, 40, 90, dtype=np.int32),
              "c": rng.random(90).astype(np.float32)}})
    cols = db.relations["R"].columns | db.relations["S"].columns
    assert not any(isinstance(c, Float64Bits) for c in cols.values())
    q = JoinQuery((Atom.of("R", "t", "p"), Atom.of("S", "t", "c")),
                  prob_var="p")
    smp = QueryEngine(db).poisson_sample(q, jax.random.key(2))
    assert {v: smp.columns[v].dtype for v in ("t", "p", "c")} == {
        "t": jnp.int32, "p": jnp.float32, "c": jnp.float32}


def test_float64bits_indexes_updates_and_decodes_exactly():
    col = place_column(SPECIAL)
    assert isinstance(col, Float64Bits) and col.bits.dtype == jnp.int64
    assert col.shape == SPECIAL.shape and col.dtype == np.float64
    assert np.asarray(col).tobytes() == SPECIAL.tobytes()
    assert np.asarray(col[2:4]).tobytes() == SPECIAL[2:4].tobytes()
    added = np.asarray(col.at[1].add(0.5))
    assert added[1] == 0.6 and added[0] == NEAR_ONE
    assert np.asarray(col.at[4:].set(0.0))[4:].tobytes() == bytes(40)
    # a root partition's pad rows get p = +0.0, its other rows p exactly
    tables = _tables()
    part = partition_root(Database.from_columns(tables), QUERY, 7)
    pads = part.rows_per_shard * 7 - 60
    last = part.shards[-1].relations["R"].column("p")
    assert isinstance(last, Float64Bits)
    assert np.asarray(last)[-pads:].tobytes() == bytes(8 * pads)
    assert np.concatenate([np.asarray(s.relations["R"].column("p"))
                           for s in part.shards])[:60].tobytes() == \
        tables["R"]["p"].tobytes()
    rel = Relation.from_numpy({"x": SPECIAL})
    taken = rel.take(jnp.asarray([4, 0, 4]))
    assert np.asarray(taken.column("x")).tobytes() == \
        SPECIAL[[4, 0, 4]].tobytes()
    both = rel.concat(rel).column("x")
    assert np.asarray(both).tobytes() == np.concatenate(
        [SPECIAL, SPECIAL]).tobytes()
