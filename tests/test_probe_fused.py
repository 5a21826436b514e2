"""Fused-GET correctness: the single-pass Pallas tree-probe kernel vs the
per-node int64 USR-GET reference (DESIGN.md §4 "Fused GET").

Property tests (hypothesis, optional via tests/_optional.py) over random
acyclic queries — chains, stars, cross-product (keyless) edges, dangling
tuples — assert the int32-narrowed fused path is *bit-identical* to
``usr_get_rows`` on every probed position, including on shreds produced by
``reshred_incremental`` (post-``apply_delta``). Plus deterministic tests of
the fallback ladder (no arena / VMEM budget / Pallas disabled) and the
engine's fused-rep selection.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _optional import HealthCheck, given, settings, st  # hypothesis or shims

from repro.core import (
    Atom, Database, DeltaBatch, JoinQuery, build_shred, get, pack_arena,
    pack_index, reshred_incremental, usr_get_rows, usr_get_rows_fused,
    usr_get_rows_paged,
)
from repro import config
from repro.core import probe
from repro.engine import QueryEngine

SET = dict(deadline=None, max_examples=20,
           suppress_health_check=[HealthCheck.too_slow])


def _shrunken(shred):
    """A policy whose VMEM budget is one word short of the shred's arena —
    the smallest budget that forces the paged rung (DESIGN.md §15)."""
    return dataclasses.replace(config.current_policy(),
                               vmem_limit=shred.packed.layout.size - 1)


def assert_fused_matches(shred, extra_random: int = 64):
    """Fused GET == per-node USR GET, bit for bit, on every position (and a
    few out-of-order random probes). When the arena can page (more than one
    page fits a one-word-short VMEM budget), the paged rung must be
    bit-identical too — same walk, streamed page by page."""
    n = int(shred.join_size)
    if n == 0 or shred.packed is None:
        return
    pos = jnp.arange(n, dtype=jnp.int64)
    rnd = jax.random.randint(jax.random.key(7), (extra_random,), 0, n
                             ).astype(jnp.int64)
    for p in (pos, rnd):
        want = usr_get_rows(shred, p)
        got = usr_get_rows_fused(shred, p)
        assert set(want) == set(got)
        for name in want:
            assert got[name].dtype == want[name].dtype, name
            np.testing.assert_array_equal(
                np.asarray(want[name]), np.asarray(got[name]), err_msg=name)
        with config.override(_shrunken(shred)):
            if not probe.paged_available(shred):
                continue  # one-page arena: no budget pages it
            paged = usr_get_rows_paged(shred, p)
        assert set(want) == set(paged)
        for name in want:
            np.testing.assert_array_equal(
                np.asarray(want[name]), np.asarray(paged[name]),
                err_msg=f"paged:{name}")


small_col = st.lists(st.integers(0, 4), min_size=0, max_size=8)


@given(a=small_col, b=small_col, c=small_col)
@settings(**SET)
def test_chain_property(a, b, c):
    m = min(len(a), len(b))
    k = min(len(b), len(c))
    db = Database.from_columns({
        "R": {"x": a[:m], "y": b[:m]},
        "S": {"y": b[:k][::-1], "z": c[:k]},  # dangling rows arise naturally
    })
    q = JoinQuery((Atom.of("R", "x", "y"), Atom.of("S", "y", "z")))
    assert_fused_matches(build_shred(db, q, rep="both"))


@given(data=st.data())
@settings(**SET)
def test_star_with_cross_product_property(data):
    """Star query with a keyless (cross-product) edge riding along."""
    def rel(ncols, name):
        n = data.draw(st.integers(1, 6), label=f"{name}_n")
        return [data.draw(st.lists(st.integers(0, 3), min_size=n, max_size=n),
                          label=f"{name}_{i}") for i in range(ncols)]

    f = rel(2, "F")
    d1 = rel(2, "D1")
    e = rel(1, "E")  # disjoint atom: joins F only via the cross product
    db = Database.from_columns({
        "F": {"a": f[0], "b": f[1]},
        "D1": {"a": d1[0], "x": d1[1]},
        "E": {"w": e[0]},
    })
    q = JoinQuery((Atom.of("F", "a", "b"), Atom.of("D1", "a", "x"),
                   Atom.of("E", "w")))
    assert_fused_matches(build_shred(db, q, rep="both"))


@given(data=st.data())
@settings(**SET)
def test_post_delta_shred_property(data):
    """Fused GET stays bit-identical on incrementally reshredded indexes."""
    def col(name, n):
        return data.draw(st.lists(st.integers(0, 3), min_size=n, max_size=n),
                         label=name)

    nr = data.draw(st.integers(1, 6), label="nr")
    ns = data.draw(st.integers(1, 6), label="ns")
    db = Database.from_columns({
        "R": {"x": col("rx", nr), "y": col("ry", nr)},
        "S": {"y": col("sy", ns), "z": col("sz", ns)},
    })
    q = JoinQuery((Atom.of("R", "x", "y"), Atom.of("S", "y", "z")))
    base = build_shred(db, q, rep="both")
    ins = data.draw(st.integers(1, 3), label="ins")
    dele = data.draw(st.integers(0, ns - 1), label="del")
    spec = {"insert": {"y": col("iy", ins), "z": col("iz", ins)}}
    if dele:
        spec["delete"] = list(range(dele))
    delta = DeltaBatch.of(S=spec)
    new = reshred_incremental(base, db, q, delta)
    scratch = build_shred(db.apply(delta), q, rep="both")
    # arena coherence: incremental == from-scratch, arena included
    assert (new.packed is None) == (scratch.packed is None)
    if new.packed is not None:
        assert new.packed.layout == scratch.packed.layout
        np.testing.assert_array_equal(np.asarray(new.packed.arena),
                                      np.asarray(scratch.packed.arena))
    assert_fused_matches(new)


class TestFallbackLadder:
    def _shred(self):
        rng = np.random.default_rng(1)
        db = Database.from_columns({
            "R": {"x": rng.integers(0, 4, 12), "y": rng.integers(0, 4, 12)},
            "S": {"y": rng.integers(0, 4, 9), "z": rng.integers(0, 4, 9)},
        })
        q = JoinQuery((Atom.of("R", "x", "y"), Atom.of("S", "y", "z")))
        return build_shred(db, q, rep="usr")

    def test_vmem_budget_falls_back(self):
        shred = self._shred()
        assert probe.fused_available(shred)
        with config.override(config.KernelPolicy(vmem_limit=1)):
            assert not probe.fused_available(shred)
            n = int(shred.join_size)
            pos = jnp.arange(n, dtype=jnp.int64)
            a = usr_get_rows(shred, pos)
            b = usr_get_rows_fused(shred, pos)  # silently takes per-node path
        for k in a:
            np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))

    def test_pallas_disable_env_falls_back(self, monkeypatch):
        shred = self._shred()
        monkeypatch.setenv("REPRO_PALLAS_DISABLE", "1")
        assert not probe.fused_available(shred)
        n = int(shred.join_size)
        pos = jnp.arange(n, dtype=jnp.int64)
        a = usr_get_rows(shred, pos)
        b = usr_get_rows_fused(shred, pos)
        for k in a:
            np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))

    def test_int32_narrowing_refused_on_overflow(self):
        """Values beyond int32 keep the int64 per-node path (DESIGN.md §9)."""
        shred = self._shred()
        root = shred.root
        big = dataclasses.replace(
            root, children=tuple(
                dataclasses.replace(
                    c, cumw_excl=c.cumw_excl + jnp.int64(2) ** 33)
                for c in root.children))
        assert pack_arena(big, shred.root_prefE) is None

    def test_empty_node_refused(self):
        db = Database.from_columns({"R": {"x": [1, 2]}, "S": {"x": [], "z": []}})
        q = JoinQuery((Atom.of("R", "x"), Atom.of("S", "x", "z")))
        shred = build_shred(db, q, rep="usr")
        assert shred.packed is None
        assert not probe.fused_available(shred)
        # (probing an empty join is out of contract for every GET path —
        # callers guard join_size == 0 before dispatching.)


class TestEngineIntegration:
    @pytest.fixture(autouse=True)
    def _prefer_pallas(self, monkeypatch):
        # The engine prefers the fused kernel by default only in compiled
        # mode (real TPU); pin the preference so the interpret-mode CI
        # exercises the fused executor path (ops.pallas_preferred).
        monkeypatch.setenv("REPRO_PALLAS_PREFER", "1")

    def _db_q(self):
        rng = np.random.default_rng(2)
        db = Database.from_columns({
            "R": {"x": rng.integers(0, 5, 20), "y": rng.integers(0, 5, 20),
                  "p": rng.random(20)},
            "S": {"y": rng.integers(0, 5, 15), "z": rng.integers(0, 5, 15)},
        })
        q = JoinQuery((Atom.of("R", "x", "y", "p"), Atom.of("S", "y", "z")),
                      prob_var="p")
        return db, q

    def test_fused_is_default_and_bit_identical(self):
        db, q = self._db_q()
        eng = QueryEngine(db)
        # Pin the multi-launch sampler: this test compares the fused GET
        # *rep* against per-node USR under one position stream (the fused
        # one-launch *draw* has its own stream — tests/test_fused_draw.py).
        plan = eng.compile(q, kernels="pernode")
        assert plan.rep_default == "usr_fused"
        key = jax.random.key(3)
        sf = plan.sample(key)
        su = plan.sample(key, rep="usr")
        np.testing.assert_array_equal(np.asarray(sf.positions),
                                      np.asarray(su.positions))
        for v in sf.columns:
            np.testing.assert_array_equal(np.asarray(sf.columns[v]),
                                          np.asarray(su.columns[v]))
        assert int(sf.count) == int(su.count)

    def test_csr_engine_keeps_csr(self):
        db, q = self._db_q()
        plan = QueryEngine(db, rep="csr").compile(q)
        assert plan.rep_default == "csr"

    def test_batched_fused_lanes_match_single(self):
        db, q = self._db_q()
        plan = QueryEngine(db).compile(q)
        keys = jax.random.split(jax.random.key(4), 3)
        sb = plan.sample_batch(keys)
        for i in range(3):
            si = plan.sample(keys[i])
            np.testing.assert_array_equal(np.asarray(sb.positions[i]),
                                          np.asarray(si.positions))

    def test_full_join_fused_matches_usr(self):
        db, q = self._db_q()
        eng = QueryEngine(db)
        plan = eng.compile(q)
        fj_f = plan.full_join()                 # rep_default == usr_fused
        fj_u = plan.full_join(rep="usr")
        for v in fj_u:
            np.testing.assert_array_equal(np.asarray(fj_f[v]),
                                          np.asarray(fj_u[v]))

    def test_apply_delta_keeps_fused_coherent(self):
        db, q = self._db_q()
        eng = QueryEngine(db)
        # kernels="pernode" keeps one position stream across the rep
        # comparison below (the fused *draw* has its own stream and its
        # delta coherence is covered by tests/test_fused_draw.py).
        plan = eng.compile(q, kernels="pernode")
        key = jax.random.key(5)
        plan.sample(key)  # warm
        eng.apply_delta(DeltaBatch.of(
            S={"insert": {"y": [1, 2], "z": [3, 0]}}))
        plan2 = eng.compile(q, kernels="pernode")
        assert plan2.rep_default == "usr_fused"
        sf = plan2.sample(key)
        su = plan2.sample(key, rep="usr")
        np.testing.assert_array_equal(np.asarray(sf.positions),
                                      np.asarray(su.positions))
        # coherence vs a cold engine on the post-delta snapshot
        cold = QueryEngine(eng.db).compile(q, kernels="pernode")
        sc = cold.sample(key)
        np.testing.assert_array_equal(np.asarray(sf.positions),
                                      np.asarray(sc.positions))


def test_reshard_reuse_restores_dropped_arena():
    """A stacked index whose arenas were dropped (mixed per-shard narrowing
    verdict in an earlier epoch) must not propagate packed=None through the
    shard-reuse path forever: reused shards re-pack, matching a
    from-scratch ``build_stacked`` of the same snapshot."""
    from repro.core.distributed import build_stacked, reshard_incremental

    rng = np.random.default_rng(11)
    db = Database.from_columns({
        "R": {"x": rng.integers(0, 5, 16), "y": rng.integers(0, 5, 16),
              "p": rng.random(16)},
        "S": {"y": rng.integers(0, 5, 10), "z": rng.integers(0, 5, 10)},
    })
    q = JoinQuery((Atom.of("R", "x", "y", "p"), Atom.of("S", "y", "z")),
                  prob_var="p")
    stacked, base = build_stacked(db, q, 2)
    assert stacked.shred.packed is not None
    stripped = dataclasses.replace(
        stacked, shred=dataclasses.replace(stacked.shred, packed=None))
    restacked, _, reused, rebuilt = reshard_incremental(
        stripped, base, db, q, 2)
    assert (reused, rebuilt) == (2, 0)  # identical snapshot: all reused
    assert restacked.shred.packed is not None
    np.testing.assert_array_equal(
        np.asarray(restacked.shred.packed.arena),
        np.asarray(stacked.shred.packed.arena))


def test_self_join_aliases():
    db = Database.from_columns({"P": {"u": list(range(6)),
                                      "g": [0, 1, 0, 2, 1, 0]}})
    q = JoinQuery((Atom.of("P", "u1", "g", alias="A"),
                   Atom.of("P", "u2", "g", alias="B")))
    assert_fused_matches(build_shred(db, q, rep="both"))


def test_deep_multi_child_tree():
    """Depth-4 tree with a 3-child interior node: exercises the per-parent
    mixed-radix peel order across interleaved pre-order edges."""
    rng = np.random.default_rng(9)
    db = Database.from_columns({
        "A": {"a": rng.integers(0, 3, 8), "b": rng.integers(0, 3, 8)},
        "B": {"b": rng.integers(0, 3, 7), "c": rng.integers(0, 3, 7),
              "d": rng.integers(0, 3, 7)},
        "C": {"c": rng.integers(0, 3, 6), "e": rng.integers(0, 3, 6)},
        "D": {"d": rng.integers(0, 3, 5), "f": rng.integers(0, 3, 5)},
        "E": {"f": rng.integers(0, 3, 4), "g": rng.integers(0, 3, 4)},
    })
    q = JoinQuery((Atom.of("A", "a", "b"), Atom.of("B", "b", "c", "d"),
                   Atom.of("C", "c", "e"), Atom.of("D", "d", "f"),
                   Atom.of("E", "f", "g")))
    assert_fused_matches(build_shred(db, q, rep="both"))


class TestPagedRung:
    """The paged rung of the kernel ladder (DESIGN.md §15): selection across
    the VMEM-budget boundaries, build-time mutual exclusivity, and the
    paged draw's bit-identity to the reference pipeline."""

    def _db_q(self):
        rng = np.random.default_rng(0)
        m = 120
        db = Database.from_columns({
            "R": {"x": rng.integers(0, 20, m), "y": rng.integers(0, 20, m),
                  "p": rng.uniform(0.05, 0.3, m)},
            "S": {"y": rng.integers(0, 20, m), "z": rng.integers(0, 20, m)},
            "T": {"z": rng.integers(0, 20, m), "u": rng.integers(0, 20, m)},
        })
        q = JoinQuery((Atom.of("R", "x", "y", "p"), Atom.of("S", "y", "z"),
                       Atom.of("T", "z", "u")), prob_var="p")
        return db, q

    def _dparams(self, shred):
        from repro.core import sampling
        return sampling.fused_draw_params(
            shred.root.weight, shred.root.data.column("p"), shred.root_prefE)

    def test_rung_selection_across_vmem_boundaries(self):
        db, q = self._db_q()
        shred = build_shred(db, q, rep="usr")
        size = shred.packed.layout.size
        max_page = shred.packed.layout.max_page
        assert max_page < size  # multi-page arena: all three rungs reachable
        dp = self._dparams(shred)
        base = dataclasses.replace(config.current_policy(), prefer=True)
        ladder = []
        for limit in (size, size - 1, max_page, max_page - 1):
            pol = dataclasses.replace(base, vmem_limit=limit)
            with config.override(pol):
                sh = build_shred(db, q, rep="usr")
                rep = probe.select_rep(sh, "usr")
                route = probe.select_draw(sh, self._dparams(sh),
                                          method="exprace")
            ladder.append((limit, rep, route))
        assert ladder == [
            (size, "usr_fused", "fused"),
            (size - 1, "usr_paged", "paged"),
            (max_page, "usr_paged", "paged"),
            (max_page - 1, "usr", "pernode"),
        ]
        # Call-time shrink (no rebuild): an already-packed index pages too.
        with config.override(dataclasses.replace(base, vmem_limit=size - 1)):
            assert probe.select_rep(shred, "usr") == "usr_paged"
            assert probe.select_draw(shred, dp, method="exprace") == "paged"

    def test_pack_index_mutual_exclusivity(self):
        db, q = self._db_q()
        shred = build_shred(db, q, rep="usr")
        assert shred.packed is not None and shred.paged is None
        with config.override(_shrunken(shred)):
            sh = build_shred(db, q, rep="usr")
        assert sh.packed is None and sh.paged is not None
        # Pages concatenate back to exactly the monolithic arena.
        np.testing.assert_array_equal(
            np.asarray(jnp.concatenate(sh.paged.pages)),
            np.asarray(shred.packed.arena))
        assert sh.paged.layout == shred.packed.layout

    def test_explicit_paged_request_raises_out_of_regime(self):
        db, q = self._db_q()
        shred = build_shred(db, q, rep="usr")
        dp = self._dparams(shred)
        with pytest.raises(ValueError, match="paged"):
            probe.select_draw(shred, dp, method="exprace", kernels="paged")

    def test_paged_draw_matches_reference_and_fused(self):
        db, q = self._db_q()
        key = jax.random.key(11)
        eng = QueryEngine(db)
        s_fused = eng.poisson_sample(q, key, kernels="fused")
        shred = build_shred(db, q, rep="usr")
        with config.override(_shrunken(shred)):
            eng2 = QueryEngine(db)
            s_paged = eng2.poisson_sample(q, key, kernels="paged")
            s_ref = eng2.poisson_sample(q, key, kernels="reference")
        for other in (s_ref, s_fused):
            np.testing.assert_array_equal(np.asarray(s_paged.positions),
                                          np.asarray(other.positions))
            assert int(s_paged.count) == int(other.count)
            for v in s_paged.columns:
                np.testing.assert_array_equal(
                    np.asarray(s_paged.columns[v]),
                    np.asarray(other.columns[v]), err_msg=v)

    def test_post_delta_paged_coherence(self):
        """pack_index stays coherent through reshred_incremental in the
        paged regime: incremental == from-scratch, pages included."""
        db, q = self._db_q()
        shred = build_shred(db, q, rep="usr")
        with config.override(_shrunken(shred)):
            base = build_shred(db, q, rep="usr")
            assert base.paged is not None
            delta = DeltaBatch.of(S={"insert": {"y": [1, 2], "z": [3, 0]}})
            new = reshred_incremental(base, db, q, delta)
            scratch = build_shred(db.apply(delta), q, rep="usr")
            assert (new.paged is None) == (scratch.paged is None)
            if new.paged is not None:
                assert new.paged.layout == scratch.paged.layout
                for a, b in zip(new.paged.pages, scratch.paged.pages):
                    np.testing.assert_array_equal(np.asarray(a),
                                                  np.asarray(b))
            assert_fused_matches(new)

    def test_stacked_paged_coherence(self):
        """Shard stacking carries the paged form like the packed one, and
        the reuse path restores dropped pages (mirrors the packed test)."""
        from repro.core.distributed import build_stacked, reshard_incremental

        db, q = self._db_q()
        shred = build_shred(db, q, rep="usr")
        with config.override(_shrunken(shred)):
            stacked, dbase = build_stacked(db, q, 2)
            # Per-shard arenas are smaller than the global one, so shards
            # may legitimately pack monoliths; either way the two forms
            # stay mutually exclusive and stack-coherent.
            assert (stacked.shred.packed is None) or (
                stacked.shred.paged is None)
            stripped = dataclasses.replace(
                stacked, shred=dataclasses.replace(
                    stacked.shred, packed=None, paged=None))
            restacked, _, reused, rebuilt = reshard_incremental(
                stripped, dbase, db, q, 2)
            assert (reused, rebuilt) == (2, 0)
            assert (restacked.shred.packed is None) == (
                stacked.shred.packed is None)
            assert (restacked.shred.paged is None) == (
                stacked.shred.paged is None)


def test_get_rows_rep_dispatch():
    rng = np.random.default_rng(6)
    db = Database.from_columns({
        "R": {"x": rng.integers(0, 4, 10), "y": rng.integers(0, 4, 10)},
        "S": {"y": rng.integers(0, 4, 8), "z": rng.integers(0, 4, 8)},
    })
    q = JoinQuery((Atom.of("R", "x", "y"), Atom.of("S", "y", "z")))
    shred = build_shred(db, q, rep="both")
    n = int(shred.join_size)
    if n == 0:
        return
    pos = jnp.arange(n, dtype=jnp.int64)
    gf = get(shred, pos, rep="usr_fused")
    gu = get(shred, pos, rep="usr")
    gc = get(shred, pos, rep="csr")
    for v in gu:
        np.testing.assert_array_equal(np.asarray(gf[v]), np.asarray(gu[v]))
        np.testing.assert_array_equal(np.asarray(gf[v]), np.asarray(gc[v]))
