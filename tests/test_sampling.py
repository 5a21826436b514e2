"""Statistical correctness of position samplers (paper §5).

Every sampler is checked against exact Bernoulli-process statistics:
count moments and (for the non-uniform EXPRACE) per-position marginals and
pairwise joint inclusion — the strongest practical test of "independent
Bernoulli trial per tuple" semantics.
"""
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import sampling

N_SEEDS = 120


def _collect(fn, n, nseeds=N_SEEDS):
    counts, seen = [], np.zeros(n)
    jfn = jax.jit(fn)
    for s in range(nseeds):
        ps = jfn(jax.random.key(s))
        c = int(ps.count)
        counts.append(c)
        pos = np.asarray(ps.positions)[:c]
        assert (pos >= 0).all() and (pos < n).all()
        assert len(np.unique(pos)) == c, "positions must be distinct"
        seen[pos] += 1
    return np.asarray(counts), seen / nseeds


@pytest.mark.parametrize("method", ["bern", "geo", "binom", "hybrid"])
@pytest.mark.parametrize("p", [0.02, 0.3, 0.5, 0.8])
def test_uniform_count_moments(method, p):
    n, cap = 600, 768
    fn = {
        "bern": sampling.bern_positions,
        "geo": sampling.geo_positions,
        "binom": sampling.binom_positions,
        "hybrid": sampling.hybrid_positions,
    }[method]
    counts, incl = _collect(lambda k: fn(k, p, n, cap), n)
    z = (counts.mean() - n * p) / ((n * p * (1 - p)) ** 0.5 / len(counts) ** 0.5)
    assert abs(z) < 4.5, f"{method} p={p}: count mean z={z:.2f}"
    # inclusion rate across positions ~ p
    zi = (incl.mean() - p) / ((p * (1 - p) / (n * len(counts))) ** 0.5)
    assert abs(zi) < 4.5, f"{method} p={p}: inclusion z={zi:.2f}"


@pytest.mark.parametrize("method,p", [("geo", 0.0), ("geo", 1.0),
                                      ("bern", 0.0), ("bern", 1.0),
                                      ("hybrid", 0.0), ("hybrid", 1.0)])
def test_uniform_endpoints(method, p):
    n, cap = 100, 128
    fn = {"bern": sampling.bern_positions, "geo": sampling.geo_positions,
          "hybrid": sampling.hybrid_positions}[method]
    ps = jax.jit(fn, static_argnums=(2, 3))(jax.random.key(0), p, n, cap)
    assert int(ps.count) == (0 if p == 0.0 else n)
    if p == 1.0:
        assert np.array_equal(np.asarray(ps.positions)[:n], np.arange(n))


def test_geo_positions_sorted_strict():
    ps = jax.jit(sampling.geo_positions, static_argnums=(2, 3))(
        jax.random.key(1), 0.2, 5000, 2048)
    pos = np.asarray(ps.positions)[: int(ps.count)]
    assert (np.diff(pos) > 0).all()


def test_geo_overflow_flagged():
    # cap too small for p*n: must flag overflow rather than silently truncate.
    ps = jax.jit(sampling.geo_positions, static_argnums=(2, 3))(
        jax.random.key(0), 0.5, 10000, 128)
    assert bool(ps.overflow)


class TestExprace:
    def _run(self, wv, pv, cap=64, acap=128, nseeds=800):
        w = jnp.asarray(wv, jnp.int64)
        p = jnp.asarray(pv, jnp.float64)
        prefE = jnp.concatenate([jnp.zeros(1, jnp.int64), jnp.cumsum(w)])
        nf = int(prefE[-1])
        massE = sampling.exprace_mass(w, p)
        fn = jax.jit(partial(sampling.exprace_positions, cap=cap, arrival_cap=acap))
        seen = np.zeros(nf)
        pair = np.zeros((nf, nf))
        counts = []
        for s in range(nseeds):
            ps = fn(jax.random.key(s), w, p, prefE, massE)
            assert not bool(ps.overflow)
            c = int(ps.count)
            counts.append(c)
            pos = np.asarray(ps.positions)[:c]
            assert (pos >= 0).all() and (pos < nf).all()
            assert (np.diff(pos) > 0).all(), "sorted distinct"
            seen[pos] += 1
            m = np.zeros(nf)
            m[pos] = 1
            pair += np.outer(m, m)
        rootid = np.searchsorted(np.asarray(prefE), np.arange(nf), side="right") - 1
        pexp = np.asarray(p)[rootid]
        zm = (seen / nseeds - pexp) / np.maximum((pexp * (1 - pexp) / nseeds) ** 0.5, 1e-9)
        eij = np.outer(pexp, pexp)
        np.fill_diagonal(eij, pexp)
        zp = (pair / nseeds - eij) / np.maximum((eij * (1 - eij) / nseeds) ** 0.5, 1e-9)
        np.fill_diagonal(zp, 0)
        return np.asarray(counts), np.abs(zm).max(), np.abs(zp).max(), float(np.sum(np.asarray(w) * np.asarray(p)))

    def test_marginals_and_pairwise_exact(self):
        counts, zm, zp, exp = self._run([8, 5, 3, 7, 1, 4], [0.35, 0.9, 1.0, 0.0, 0.5, 0.75])
        z = (counts.mean() - exp) / (counts.std(ddof=1) / len(counts) ** 0.5)
        assert abs(z) < 4.5
        assert zm < 5.0, f"marginal inclusion bias: max|z|={zm:.2f}"
        assert zp < 5.5, f"pairwise dependence: max|z|={zp:.2f}"

    def test_complement_path_high_p(self):
        counts, zm, zp, exp = self._run([10, 6], [0.97, 0.85], nseeds=600)
        assert zm < 5.0 and zp < 5.5

    def test_endpoint_probabilities_deterministic(self):
        w = jnp.asarray([5, 4], jnp.int64)
        p = jnp.asarray([1.0, 0.0], jnp.float64)
        prefE = jnp.asarray([0, 5, 9], jnp.int64)
        ps = jax.jit(partial(sampling.exprace_positions, cap=16, arrival_cap=16))(
            jax.random.key(0), w, p, prefE, sampling.exprace_mass(w, p))
        assert int(ps.count) == 5
        assert np.array_equal(np.asarray(ps.positions)[:5], np.arange(5))

    def test_zero_weight_roots_never_sampled(self):
        w = jnp.asarray([0, 6, 0], jnp.int64)
        p = jnp.asarray([1.0, 0.5, 1.0], jnp.float64)
        prefE = jnp.asarray([0, 0, 6, 6], jnp.int64)
        fn = jax.jit(partial(sampling.exprace_positions, cap=16, arrival_cap=32))
        massE = sampling.exprace_mass(w, p)
        for s in range(50):
            ps = fn(jax.random.key(s), w, p, prefE, massE)
            pos = np.asarray(ps.positions)[: int(ps.count)]
            assert (pos < 6).all()

    def test_host_mass_matches_device_scan(self):
        """The host numpy mass prefix plans bind agrees with the f64 device
        scan of the same rates (summation order differs: rtol 1e-12), and
        a stacked (S, R) input is the per-shard prefix."""
        rng = np.random.default_rng(5)
        w = rng.integers(0, 40, (3, 257))
        p = rng.random((3, 257))
        p[:, :5] = [0.0, 0.5, 0.51, 1.0, 0.999]
        massE = sampling.exprace_mass(w, p)
        assert massE.shape == (3, 258) and massE.dtype == np.float64
        for s in range(3):
            _, lam = sampling._exprace_rates(jnp.asarray(p[s]))
            dev = np.concatenate([[0.0], np.asarray(
                jnp.cumsum(jnp.asarray(w[s], jnp.float64) * lam))])
            np.testing.assert_allclose(massE[s], dev, rtol=1e-12, atol=0)
            np.testing.assert_array_equal(
                massE[s], sampling.exprace_mass(w[s], p[s]))

    def test_matches_host_oracle_distribution(self):
        """EXPRACE count distribution == paper-faithful sequential PT* oracle."""
        wv, pv = [12, 9, 20], [0.15, 0.6, 0.33]
        w = jnp.asarray(wv, jnp.int64)
        p = jnp.asarray(pv, jnp.float64)
        prefE = jnp.concatenate([jnp.zeros(1, jnp.int64), jnp.cumsum(w)])
        fn = jax.jit(partial(sampling.exprace_positions, cap=64, arrival_cap=96))
        massE = sampling.exprace_mass(w, p)
        ours = [int(fn(jax.random.key(s), w, p, prefE, massE).count)
                for s in range(400)]
        rng = np.random.default_rng(0)
        host = [len(sampling.pt_positions_host(rng, wv, pv, "hybrid")) for _ in range(400)]
        # two-sample z-test on means
        se = (np.var(ours) / 400 + np.var(host) / 400) ** 0.5
        z = (np.mean(ours) - np.mean(host)) / max(se, 1e-9)
        assert abs(z) < 4.5, f"EXPRACE vs host oracle: z={z:.2f}"


def test_host_oracle_methods_agree():
    rng = np.random.default_rng(1)
    w, p = [30, 40], [0.2, 0.45]
    means = {}
    for m in ("bern", "geo", "hybrid"):
        ks = [len(sampling.pt_positions_host(rng, w, p, m)) for _ in range(300)]
        means[m] = np.mean(ks)
    exp = 30 * 0.2 + 40 * 0.45
    for m, v in means.items():
        assert abs(v - exp) < 3.0, (m, v, exp)
