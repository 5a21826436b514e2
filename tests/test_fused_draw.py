"""One-launch fused draw correctness (DESIGN.md §14).

Property tests (hypothesis, optional via tests/_optional.py) over random
acyclic queries — chains, cross-product (keyless) edges, dangling tuples,
post-``apply_delta`` shreds — assert the Pallas kernel
(``kernels.fused_draw.fused_draw``) is *bit-identical* to its multi-launch
reference (``fused_draw_ref``: the same ``draw_core`` + ``tree_walk`` as
plain traced jnp) for both EXPRACE and flat PTBERN. Plus deterministic
tests of the fallback ladder (``probe.select_draw``), the ``KernelPolicy``
resolution order (per-call > ``override(...)`` > env), and the engine
route integration (``DrawSpec.kernels``).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _optional import HealthCheck, given, settings, st  # hypothesis or shims

from repro import config
from repro.core import (
    Atom, Database, DeltaBatch, JoinQuery, build_shred, probe,
    reshred_incremental, sampling,
)
from repro.engine import QueryEngine
from repro.kernels.fused_draw import fused_draw, fused_draw_ref

SET = dict(deadline=None, max_examples=15,
           suppress_health_check=[HealthCheck.too_slow])


def assert_fused_draw_matches(shred, p=None, seeds=(0, 1)):
    """Kernel == reference, bit for bit: positions, count, overflow, and
    every per-node row vector, for EXPRACE and (small n) flat PTBERN."""
    n = int(shred.join_size)
    if n == 0 or shred.packed is None:
        return
    R = int(shred.root.num_rows)
    if p is None:
        rng = np.random.default_rng(R * 7919 + n)
        p = jnp.asarray(np.clip(rng.random(R), 0.02, 0.98))
    dparams = sampling.fused_draw_params(
        shred.root.weight, p, shred.root_prefE)
    assert dparams is not None
    packed = shred.packed
    cap = max(8, n + 4)
    acap = 2 * cap + 8
    for seed in seeds:
        key = jax.random.key_data(jax.random.key(seed)).astype(jnp.uint32)
        for method, kw in (("exprace", dict(acap=acap)),
                           ("ptbern_flat", dict(n=n))):
            got = fused_draw(packed.arena, key, dparams,
                             layout=packed.layout, method=method, cap=cap,
                             interpret=True, **kw)
            want = fused_draw_ref(packed.arena, key, dparams,
                                  layout=packed.layout, method=method,
                                  cap=cap, **kw)
            for g, w, what in zip(got, want,
                                  ("rows", "positions", "count", "overflow")):
                np.testing.assert_array_equal(
                    np.asarray(g), np.asarray(w),
                    err_msg=f"{method}/{what}/seed={seed}")
            # Positions are ascending over valid lanes, sentinel n beyond.
            pos, cnt = np.asarray(got[1]), int(got[2])
            assert (np.diff(pos[:cnt]) >= 0).all(), method
            assert (pos[cnt:] == n).all(), method


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
    assert_fused_draw_matches(build_shred(db, q, rep="usr"))


@given(data=st.data())
@settings(**SET)
def test_cross_product_and_extreme_p_property(data):
    """Keyless (cross-product) edge + probabilities spanning both EXPRACE
    regimes (direct p <= 1/2 and the complement inversion p > 1/2)."""
    nf = data.draw(st.integers(1, 5), label="nf")
    ne = data.draw(st.integers(1, 4), label="ne")
    db = Database.from_columns({
        "F": {"a": data.draw(st.lists(st.integers(0, 3), min_size=nf,
                                      max_size=nf), label="fa")},
        "E": {"w": data.draw(st.lists(st.integers(0, 3), min_size=ne,
                                      max_size=ne), label="ew")},
    })
    q = JoinQuery((Atom.of("F", "a"), Atom.of("E", "w")))
    shred = build_shred(db, q, rep="usr")
    # One probability per row of the join tree's root (GYO may root the
    # tree at either relation).
    nroot = shred.root.num_rows
    p = jnp.asarray(data.draw(
        st.lists(st.sampled_from([0.01, 0.3, 0.5, 0.7, 0.99]),
                 min_size=nroot, max_size=nroot), label="p"))
    assert_fused_draw_matches(shred, p=p)


@given(data=st.data())
@settings(**SET)
def test_post_delta_shred_property(data):
    """Fused draw stays bit-identical on incrementally reshredded
    indexes (the arena a delta rebuilt, not the one build_shred made)."""
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
    base = build_shred(db, q, rep="usr")
    ins = data.draw(st.integers(1, 3), label="ins")
    delta = DeltaBatch.of(S={"insert": {"y": col("iy", ins),
                                        "z": col("iz", ins)}})
    assert_fused_draw_matches(reshred_incremental(base, db, q, delta))


# ---------------------------------------------------------------------------
# Deterministic twins of the properties above — hypothesis is optional in
# the container, and the bit-identity guarantee must hold regardless.
# ---------------------------------------------------------------------------

class TestBitIdentityDeterministic:
    def test_chain(self):
        for seed in range(4):
            rng = np.random.default_rng(seed)
            nr, ns = int(rng.integers(2, 14)), int(rng.integers(2, 12))
            db = Database.from_columns({
                "R": {"x": rng.integers(0, 4, nr),
                      "y": rng.integers(0, 4, nr)},
                "S": {"y": rng.integers(0, 4, ns),
                      "z": rng.integers(0, 4, ns)},
            })
            q = JoinQuery((Atom.of("R", "x", "y"), Atom.of("S", "y", "z")))
            assert_fused_draw_matches(build_shred(db, q, rep="usr"))

    def test_three_way_star(self):
        rng = np.random.default_rng(42)
        db = Database.from_columns({
            "R": {"x": rng.integers(0, 3, 10), "y": rng.integers(0, 3, 10)},
            "S": {"y": rng.integers(0, 3, 9), "z": rng.integers(0, 3, 9)},
            "T": {"y": rng.integers(0, 3, 7), "u": rng.integers(0, 3, 7)},
        })
        q = JoinQuery((Atom.of("R", "x", "y"), Atom.of("S", "y", "z"),
                       Atom.of("T", "y", "u")))
        assert_fused_draw_matches(build_shred(db, q, rep="usr"))

    def test_cross_product_extreme_p(self):
        db = Database.from_columns({
            "F": {"a": [0, 1, 2, 3]},
            "E": {"w": [5, 6, 7]},
        })
        q = JoinQuery((Atom.of("F", "a"), Atom.of("E", "w")))
        shred = build_shred(db, q, rep="usr")
        for pv in ([0.01, 0.3, 0.5, 0.99], [0.99, 0.98, 0.97, 0.96],
                   [0.5, 0.5, 0.5, 0.5]):
            p = jnp.asarray(pv[:int(shred.root.num_rows)])
            assert_fused_draw_matches(shred, p=p)

    def test_dangling_tuples(self):
        db = Database.from_columns({
            "R": {"x": [0, 1, 2, 3, 4], "y": [0, 1, 2, 9, 9]},  # 9s dangle
            "S": {"y": [0, 1, 2, 2, 7], "z": [0, 1, 2, 3, 4]},
        })
        q = JoinQuery((Atom.of("R", "x", "y"), Atom.of("S", "y", "z")))
        assert_fused_draw_matches(build_shred(db, q, rep="usr"))

    def test_post_delta_shred(self):
        rng = np.random.default_rng(7)
        db = Database.from_columns({
            "R": {"x": rng.integers(0, 3, 8), "y": rng.integers(0, 3, 8)},
            "S": {"y": rng.integers(0, 3, 6), "z": rng.integers(0, 3, 6)},
        })
        q = JoinQuery((Atom.of("R", "x", "y"), Atom.of("S", "y", "z")))
        base = build_shred(db, q, rep="usr")
        delta = DeltaBatch.of(S={"insert": {"y": [1, 2, 0], "z": [3, 3, 3]}})
        assert_fused_draw_matches(reshred_incremental(base, db, q, delta))


# ---------------------------------------------------------------------------
# Fallback ladder / route selection
# ---------------------------------------------------------------------------

def _shred_p(seed=3, nr=14, ns=10):
    rng = np.random.default_rng(seed)
    db = Database.from_columns({
        "R": {"x": rng.integers(0, 4, nr), "y": rng.integers(0, 4, nr)},
        "S": {"y": rng.integers(0, 4, ns), "z": rng.integers(0, 4, ns)},
    })
    q = JoinQuery((Atom.of("R", "x", "y"), Atom.of("S", "y", "z")))
    shred = build_shred(db, q, rep="usr")
    p = jnp.asarray(np.clip(rng.random(int(shred.root.num_rows)), 0.05, 0.9))
    return shred, p


class TestSelectDraw:
    def _dparams(self, shred, p):
        return sampling.fused_draw_params(shred.root.weight, p,
                                          shred.root_prefE)

    def test_auto_needs_preference(self):
        shred, p = _shred_p()
        dp = self._dparams(shred, p)
        base = config.KernelPolicy()  # interpret, no prefer -> pernode
        assert probe.select_draw(shred, dp, method="exprace",
                                 policy=base) == "pernode"
        assert probe.select_draw(
            shred, dp, method="exprace",
            policy=config.KernelPolicy(prefer=True)) == "fused"
        assert probe.select_draw(
            shred, dp, method="exprace",
            policy=config.KernelPolicy(interpret=False)) == "fused"

    def test_fused_draw_optout(self):
        shred, p = _shred_p()
        dp = self._dparams(shred, p)
        pol = config.KernelPolicy(prefer=True, fused_draw=False)
        assert probe.select_draw(shred, dp, method="exprace",
                                 policy=pol) == "pernode"

    def test_vmem_budget_falls_back(self):
        shred, p = _shred_p()
        dp = self._dparams(shred, p)
        pol = config.KernelPolicy(prefer=True, vmem_limit=1)
        assert probe.select_draw(shred, dp, method="exprace",
                                 policy=pol) == "pernode"
        with pytest.raises(ValueError):
            probe.select_draw(shred, dp, method="exprace", kernels="fused",
                              policy=pol)

    def test_no_params_falls_back(self):
        shred, p = _shred_p()
        pol = config.KernelPolicy(prefer=True)
        assert probe.select_draw(shred, None, method="exprace",
                                 policy=pol) == "pernode"
        with pytest.raises(ValueError):
            probe.select_draw(shred, None, method="exprace",
                              kernels="reference", policy=pol)

    def test_ptbern_n_budget(self):
        shred, p = _shred_p()
        dp = self._dparams(shred, p)
        n = int(shred.join_size)
        pol = config.KernelPolicy(prefer=True, vmem_limit=max(n, 64))
        assert probe.select_draw(shred, dp, method="ptbern_flat", n=n,
                                 policy=pol) == "fused"
        tight = config.KernelPolicy(prefer=True, vmem_limit=max(n // 2, 1))
        # n over the budget: Theta(n) lanes no longer fit VMEM.
        if n > 1 and shred.packed.layout.size <= max(n // 2, 1):
            assert probe.select_draw(shred, dp, method="ptbern_flat", n=n,
                                     policy=tight) == "pernode"

    def test_explicit_pernode_always_honored(self):
        shred, p = _shred_p()
        dp = self._dparams(shred, p)
        pol = config.KernelPolicy(prefer=True)
        assert probe.select_draw(shred, dp, method="exprace",
                                 kernels="pernode", policy=pol) == "pernode"

    def test_reference_runs_with_kernels_disabled(self):
        shred, p = _shred_p()
        dp = self._dparams(shred, p)
        pol = config.KernelPolicy(enabled=False)
        assert probe.select_draw(shred, dp, method="exprace",
                                 kernels="reference", policy=pol) == "reference"
        with pytest.raises(ValueError):
            probe.select_draw(shred, dp, method="exprace", kernels="fused",
                              policy=pol)


# ---------------------------------------------------------------------------
# KernelPolicy resolution order
# ---------------------------------------------------------------------------

class TestKernelPolicy:
    def test_env_is_default_constructor(self, monkeypatch):
        monkeypatch.setenv("REPRO_PALLAS_DISABLE", "1")
        assert not config.current_policy().enabled
        monkeypatch.setenv("REPRO_PALLAS_DISABLE", "0")
        assert config.current_policy().enabled
        # Empty-string semantics: INTERPRET='' means the backend default
        # (interpret on this CPU backend), PREFER='' means False.
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "")
        monkeypatch.setenv("REPRO_PALLAS_PREFER", "")
        pol = config.current_policy()
        assert pol.interpret and not pol.prefer and not pol.preferred
        monkeypatch.setenv("REPRO_PALLAS_PREFER", "1")
        assert config.current_policy().preferred

    def test_override_beats_env(self, monkeypatch):
        monkeypatch.setenv("REPRO_PALLAS_DISABLE", "1")
        with config.override(config.KernelPolicy(enabled=True)):
            assert config.current_policy().enabled
        assert not config.current_policy().enabled

    def test_per_call_beats_override(self):
        with config.override(config.KernelPolicy(enabled=False)):
            pol = config.KernelPolicy(enabled=True, prefer=True)
            assert config.current_policy(pol).preferred
        # Contexts nest and unwind.
        assert config.current_policy().enabled

    def test_preferred_property(self):
        assert config.KernelPolicy(interpret=False).preferred
        assert config.KernelPolicy(interpret=True, prefer=True).preferred
        assert not config.KernelPolicy(interpret=True).preferred
        assert not config.KernelPolicy(enabled=False,
                                       interpret=False).preferred

    def test_bench_tiny_helpers(self, monkeypatch):
        # monkeypatch records the pre-test value and restores at teardown,
        # even though set_bench_tiny mutates the env directly in config.py.
        monkeypatch.setenv("REPRO_BENCH_TINY", "0")
        config.set_bench_tiny(True)
        assert config.bench_tiny()
        config.set_bench_tiny(False)
        assert not config.bench_tiny()


# ---------------------------------------------------------------------------
# Engine route integration (DrawSpec.kernels)
# ---------------------------------------------------------------------------

class TestEngineRoutes:
    def _db_q(self):
        rng = np.random.default_rng(9)
        db = Database.from_columns({
            "R": {"x": rng.integers(0, 5, 24), "y": rng.integers(0, 5, 24),
                  "p": np.clip(rng.random(24), 0.05, 0.9)},
            "S": {"y": rng.integers(0, 5, 18), "z": rng.integers(0, 5, 18)},
        })
        q = JoinQuery((Atom.of("R", "x", "y", "p"), Atom.of("S", "y", "z")),
                      prob_var="p")
        return db, q

    def test_auto_routes_fused_under_preference(self):
        db, q = self._db_q()
        with config.override(config.KernelPolicy(prefer=True)):
            eng = QueryEngine(db)
            plan = eng.compile(q)
            assert plan._route == "fused"
            key = jax.random.key(11)
            sf = plan.sample(key)
            sref = eng.poisson_sample(q, key, kernels="reference")
            np.testing.assert_array_equal(np.asarray(sf.positions),
                                          np.asarray(sref.positions))
            assert int(sf.count) == int(sref.count)
            for v in sf.columns:
                np.testing.assert_array_equal(np.asarray(sf.columns[v]),
                                              np.asarray(sref.columns[v]))

    def test_auto_stays_pernode_without_preference(self):
        db, q = self._db_q()
        # Pin the default policy: the CI interpret leg exports
        # REPRO_PALLAS_PREFER=1, which would flip the auto route.
        with config.override(config.KernelPolicy()):
            plan = QueryEngine(db).compile(q)
        assert plan._route == "pernode"

    def test_kernels_is_plan_identity(self):
        db, q = self._db_q()
        eng = QueryEngine(db)
        a = eng.compile(q, kernels="pernode")
        b = eng.compile(q, kernels="reference")
        assert a is not b
        assert eng.compile(q, kernels="pernode") is a  # warm hit

    def test_batched_fused_lanes_match_single(self):
        db, q = self._db_q()
        with config.override(config.KernelPolicy(prefer=True)):
            plan = QueryEngine(db).compile(q)
            assert plan._route == "fused"
            keys = jax.random.split(jax.random.key(12), 5)
            sb = plan.sample_batch(keys)
            for i in range(5):
                si = plan.sample(keys[i])
                np.testing.assert_array_equal(np.asarray(sb.positions[i]),
                                              np.asarray(si.positions))
                assert int(sb.count[i]) == int(si.count)

    def test_apply_delta_rebinds_route(self):
        db, q = self._db_q()
        with config.override(config.KernelPolicy(prefer=True)):
            eng = QueryEngine(db)
            plan = eng.compile(q)
            key = jax.random.key(13)
            plan.sample(key)  # warm
            eng.apply_delta(DeltaBatch.of(
                S={"insert": {"y": [1, 3], "z": [0, 2]}}))
            plan2 = eng.compile(q)
            assert plan2._route == "fused"
            # warm upgraded plan == cold engine on the post-delta snapshot
            sf = plan2.sample(key)
            sc = QueryEngine(eng.db).compile(q).sample(key)
            np.testing.assert_array_equal(np.asarray(sf.positions),
                                          np.asarray(sc.positions))

    def test_explicit_fused_without_preference(self):
        """kernels='fused' bypasses the preference gate (capability and
        enablement still required)."""
        db, q = self._db_q()
        eng = QueryEngine(db)
        plan = eng.compile(q, kernels="fused")
        assert plan._route == "fused"
        s = plan.sample(jax.random.key(14))
        assert int(s.count) >= 0

    def test_explicit_fused_raises_when_disabled(self):
        db, q = self._db_q()
        with config.override(config.KernelPolicy(enabled=False)):
            with pytest.raises(ValueError, match="fused"):
                QueryEngine(db).compile(q, kernels="fused")

    def test_ptbern_fused_matches_reference(self):
        db, q = self._db_q()
        with config.override(config.KernelPolicy(prefer=True)):
            eng = QueryEngine(db)
            plan = eng.compile(q, method="ptbern_flat")
            assert plan._route == "fused"
            key = jax.random.key(15)
            sf = plan.sample(key)
            sref = eng.poisson_sample(q, key, method="ptbern_flat",
                                      kernels="reference")
            np.testing.assert_array_equal(np.asarray(sf.positions),
                                          np.asarray(sref.positions))

    def test_per_call_rep_pins_pernode(self):
        """An explicit rep override draws from the per-node sampler (the
        fused kernel has no rep), matching the no-preference stream."""
        db, q = self._db_q()
        with config.override(config.KernelPolicy(prefer=True)):
            plan = QueryEngine(db).compile(q)
            assert plan._route == "fused"
            key = jax.random.key(16)
            s_rep = plan.sample(key, rep="usr")
        s_pn = QueryEngine(db).compile(q, kernels="pernode").sample(key)
        np.testing.assert_array_equal(np.asarray(s_rep.positions),
                                      np.asarray(s_pn.positions))
