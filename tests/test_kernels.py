"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracles,
sweeping shapes and dtypes, plus hypothesis property tests (which skip
gracefully when hypothesis is not installed — see tests/_optional.py)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _optional import given, settings, st  # hypothesis, or skip shims

from repro.kernels import ops
from repro.kernels import ref


SET = dict(deadline=None, max_examples=12)


class TestBsearchProbe:
    @pytest.mark.parametrize("np_len", [2, 7, 64, 129, 1000])
    @pytest.mark.parametrize("nq", [1, 5, 128, 300])
    def test_matches_ref(self, np_len, nq):
        rng = np.random.default_rng(np_len * 1000 + nq)
        w = rng.integers(0, 5, np_len - 1)
        pref = jnp.asarray(np.concatenate([[0], np.cumsum(w)]), jnp.int32)
        total = int(pref[-1])
        q = jnp.asarray(rng.integers(0, max(total, 1), nq), jnp.int32)
        got = ops.searchsorted_prefix(pref, q)
        want = ref.bsearch_probe_ref(pref, q.reshape(1, -1)).reshape(-1)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_int64_fallback(self):
        pref = jnp.asarray([0, 2**33, 2**34], jnp.int64)
        q = jnp.asarray([0, 2**33 - 1, 2**33, 2**34 - 1], jnp.int64)
        got = ops.searchsorted_prefix(pref, q)
        np.testing.assert_array_equal(np.asarray(got), [0, 0, 1, 1])

    @given(st.lists(st.integers(0, 10), min_size=1, max_size=200))
    @settings(**SET)
    def test_property_random_weights(self, ws):
        pref = jnp.asarray(np.concatenate([[0], np.cumsum(ws)]), jnp.int32)
        total = int(pref[-1])
        if total == 0:
            return  # empty position space: nothing to probe
        q = jnp.arange(total, dtype=jnp.int32)
        got = np.asarray(ops.searchsorted_prefix(pref, q))
        want = np.asarray(ref.bsearch_probe_ref(pref, q.reshape(1, -1))).reshape(-1)
        np.testing.assert_array_equal(got, want)
        # semantic invariant: pref[j] <= q < pref[j + 1]
        prefn = np.asarray(pref)
        assert (prefn[got] <= np.asarray(q)).all()
        assert (np.asarray(q) < prefn[got + 1]).all()


class TestKarySearchsorted:
    """The row-gather search tree equals numpy's binary search exactly:
    every table size around the fan-out boundaries, runs of duplicates,
    queries outside the table, both sides, int and float dtypes."""

    @pytest.mark.parametrize("n", [0, 1, 5, 128, 129, 16387, 70001])
    @pytest.mark.parametrize("side", ["left", "right"])
    @pytest.mark.parametrize("dtype", [np.int64, np.int32, np.float64])
    def test_matches_numpy(self, n, side, dtype):
        rng = np.random.default_rng(n)
        hi = 40 if n < 2000 else 10 ** 9  # small tables: long equal runs
        t = np.sort(rng.integers(0, hi, n)).astype(dtype)
        q = rng.integers(-2, hi + 3, 5003).astype(dtype)
        got = ops.kary_searchsorted(jnp.asarray(t), jnp.asarray(q), side,
                                    chunk=1024)
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got),
                                      np.searchsorted(t, q, side))

    def test_dtype_max_query_and_small_fan(self):
        t = np.array([0, 3, 3, 9], np.int64)
        q = np.array([np.iinfo(np.int64).max, -1, 3, 9], np.int64)
        for fan in (2, 3, 128):
            got = ops.kary_searchsorted(jnp.asarray(t), jnp.asarray(q),
                                        fan=fan)
            np.testing.assert_array_equal(np.asarray(got), [4, 0, 3, 4])


class TestOpsDispatch:
    """Call-time behavior of the ops wrappers: env flags are read per call
    (not frozen at import), explicit ``interpret=`` overrides win, and
    ``REPRO_PALLAS_DISABLE`` forces the XLA fallback."""

    def _pref_q(self):
        pref = jnp.asarray(np.concatenate([[0], np.cumsum([2, 3, 1, 4])]),
                           jnp.int32)
        q = jnp.asarray([0, 1, 2, 5, 9], jnp.int32)
        return pref, q

    def test_interpret_env_read_at_call_time(self, monkeypatch):
        pref, q = self._pref_q()
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
        assert ops.interpret_default()
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
        assert not ops.interpret_default()  # no re-import needed

    def test_explicit_interpret_overrides_env(self, monkeypatch):
        # env says compiled mode (which this CPU container cannot lower);
        # the per-call override must still take the interpreter path.
        pref, q = self._pref_q()
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
        got = ops.searchsorted_prefix(pref, q, interpret=True)
        want = ref.bsearch_probe_ref(pref, q.reshape(1, -1)).reshape(-1)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_disable_env_forces_fallback(self, monkeypatch):
        pref, q = self._pref_q()
        monkeypatch.setenv("REPRO_PALLAS_DISABLE", "1")
        assert not ops.pallas_enabled()
        assert not ops.pallas_preferred()
        got = ops.searchsorted_prefix(pref, q)  # pure-XLA path
        want = ref.bsearch_probe_ref(pref, q.reshape(1, -1)).reshape(-1)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_disable_env_covers_geo_and_attention(self, monkeypatch):
        # The disable escape hatch must cover EVERY wrapper, not only the
        # index kernels: GEO and attention fall back to their ref oracles.
        u = jax.random.uniform(jax.random.key(0), (300,), jnp.float32,
                               minval=1e-6, maxval=1.0 - 1e-6)
        ks = jax.random.split(jax.random.key(1), 3)
        q = jax.random.normal(ks[0], (1, 2, 32), jnp.float32)
        k = jax.random.normal(ks[1], (1, 2, 256, 32), jnp.float32)
        v = jax.random.normal(ks[2], (1, 2, 256, 32), jnp.float32)
        qs = q[:, :, None, :].repeat(256, axis=2)
        on = (np.asarray(ops.geo_positions_fused(u, 0.1)),
              np.asarray(ops.decode_attention(q, k, v, block_s=128)),
              np.asarray(ops.prefill_attention(qs, k, v, block_q=128,
                                               block_k=128)))
        monkeypatch.setenv("REPRO_PALLAS_DISABLE", "1")
        off = (np.asarray(ops.geo_positions_fused(u, 0.1)),
               np.asarray(ops.decode_attention(q, k, v, block_s=128)),
               np.asarray(ops.prefill_attention(qs, k, v, block_q=128,
                                                block_k=128)))
        np.testing.assert_array_equal(on[0], off[0])
        np.testing.assert_allclose(on[1], off[1], rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(on[2], off[2], rtol=2e-4, atol=2e-4)

    def test_float_prefix_takes_fallback(self):
        # EXPRACE's inverse-CDF search over the float mass vector: dtypes
        # never permit the int32 kernel; the fallback must be exact.
        pref = jnp.asarray([0.0, 1.5, 2.25, 7.0], jnp.float64)
        q = jnp.asarray([0.0, 1.4999, 1.5, 6.9999, 7.5], jnp.float64)
        got = ops.searchsorted_prefix(pref, q)
        np.testing.assert_array_equal(np.asarray(got), [0, 0, 1, 2, 3])


class TestTreeProbe:
    """Fused tree-probe kernel vs the per-node USR walk (bit-identity over
    full join shapes lives in tests/test_probe_fused.py; this is the
    kernel-level shape/tiling sweep)."""

    def _shred(self, seed, nr, ns):
        from repro.core import Atom, Database, JoinQuery, build_shred
        rng = np.random.default_rng(seed)
        db = Database.from_columns({
            "R": {"x": rng.integers(0, 6, nr), "y": rng.integers(0, 6, nr)},
            "S": {"y": rng.integers(0, 6, ns), "z": rng.integers(0, 6, ns)},
        })
        q = JoinQuery((Atom.of("R", "x", "y"), Atom.of("S", "y", "z")))
        return build_shred(db, q, rep="usr")

    @pytest.mark.parametrize("k", [1, 127, 128, 129, 1000])
    @pytest.mark.parametrize("block_rows", [1, 8])
    def test_matches_per_node_across_tilings(self, k, block_rows):
        from repro.core.probe import usr_get_rows
        from repro.kernels.tree_probe import tree_probe
        shred = self._shred(k, 40, 30)
        assert shred.packed is not None
        n = int(shred.join_size)
        pos = jnp.asarray(np.random.default_rng(k).integers(0, n, k))
        want = usr_get_rows(shred, pos)
        tiles = ops.to_tiles(pos.astype(jnp.int32))
        out = tree_probe(shred.packed.arena, tiles,
                         layout=shred.packed.layout, block_rows=block_rows,
                         interpret=True)
        flat = np.asarray(out.reshape(out.shape[0], -1)[:, :k])
        for i, name in enumerate(shred.packed.layout.names):
            np.testing.assert_array_equal(flat[i], np.asarray(want[name]),
                                          err_msg=name)


class TestPrefixSum:
    @pytest.mark.parametrize("n", [1, 127, 128, 129, 8192, 10000])
    @pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
    def test_matches_ref(self, n, dtype):
        rng = np.random.default_rng(n)
        x = jnp.asarray(rng.integers(0, 9, n), dtype)
        got = np.asarray(ops.prefix_sum(x))
        want = np.cumsum(np.asarray(x)).astype(np.asarray(x).dtype)
        np.testing.assert_allclose(got, want, rtol=1e-6)

    def test_exclusive(self):
        x = jnp.asarray([3, 1, 4, 1, 5], jnp.int32)
        got = np.asarray(ops.prefix_sum(x, exclusive=True))
        np.testing.assert_array_equal(got, [0, 3, 4, 8, 9])

    def test_int64_fallback(self):
        x = jnp.asarray([2**32, 2**32, 1], jnp.int64)
        got = np.asarray(ops.prefix_sum(x))
        np.testing.assert_array_equal(got, [2**32, 2**33, 2**33 + 1])

    def test_block_boundary_carry(self):
        # value exactly at tile boundaries exercises the SMEM carry chain
        n = 64 * 128 * 2 + 1
        x = jnp.ones((n,), jnp.int32)
        got = np.asarray(ops.prefix_sum(x))
        assert got[-1] == n and got[64 * 128] == 64 * 128 + 1


class TestGeoGaps:
    @pytest.mark.parametrize("n", [64, 128, 1000, 9000])
    @pytest.mark.parametrize("p", [0.001, 0.1, 0.5, 0.9])
    def test_matches_ref(self, n, p):
        u = jax.random.uniform(jax.random.key(n), (n,), jnp.float32,
                               minval=1e-6, maxval=1.0 - 1e-6)
        got = np.asarray(ops.geo_positions_fused(u, p))
        want = np.asarray(ref.geo_gaps_ref(u, p))
        np.testing.assert_array_equal(got, want)

    def test_positions_strictly_ascending(self):
        u = jax.random.uniform(jax.random.key(0), (5000,), jnp.float32,
                               minval=1e-6, maxval=1.0 - 1e-6)
        pos = np.asarray(ops.geo_positions_fused(u, 0.05))
        assert (np.diff(pos) > 0).all()


class TestFlashDecode:
    @pytest.mark.parametrize("B,H,KVH,S,D", [
        (1, 4, 4, 512, 64),
        (2, 8, 2, 1024, 64),    # GQA 4:1
        (2, 4, 1, 2048, 128),   # MQA
        (1, 2, 2, 640, 128),    # padded S (not block multiple)
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, B, H, KVH, S, D, dtype):
        ks = jax.random.split(jax.random.key(B * S + H), 3)
        q = jax.random.normal(ks[0], (B, H, D), dtype)
        k = jax.random.normal(ks[1], (B, KVH, S, D), dtype)
        v = jax.random.normal(ks[2], (B, KVH, S, D), dtype)
        got = ops.decode_attention(q, k, v)
        want = ref.flash_decode_ref(q, k, v, jnp.zeros((B, S), jnp.float32))
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)

    def test_bias_masking_matches_short_cache(self):
        """-inf bias over the tail == attention over the truncated cache."""
        B, H, S, D, L = 1, 2, 1024, 64, 700
        ks = jax.random.split(jax.random.key(0), 3)
        q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, H, S, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, H, S, D), jnp.float32)
        bias = jnp.where(jnp.arange(S)[None, :] < L, 0.0, -1e30).astype(jnp.float32)
        got = ops.decode_attention(q, k, v, bias)
        want = ref.flash_decode_ref(q, k[:, :, :L], v[:, :, :L],
                                    jnp.zeros((B, L), jnp.float32))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_softmax_normalization(self):
        """With v == 1, attention output must be exactly 1 (partition check)."""
        B, H, S, D = 1, 2, 512, 64
        q = jax.random.normal(jax.random.key(1), (B, H, D), jnp.float32)
        k = jax.random.normal(jax.random.key(2), (B, H, S, D), jnp.float32)
        v = jnp.ones((B, H, S, D), jnp.float32)
        got = ops.decode_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(got), 1.0, rtol=1e-5)


class TestFlashPrefill:
    @pytest.mark.parametrize("B,H,KVH,S,D", [
        (1, 4, 4, 512, 64),
        (2, 8, 2, 512, 64),     # GQA 4:1
        (1, 4, 1, 1536, 128),   # MQA, padded S (not an lcm multiple)
    ])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_ref(self, B, H, KVH, S, D, causal):
        ks = jax.random.split(jax.random.key(S + H), 3)
        q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, KVH, S, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, KVH, S, D), jnp.float32)
        got = ops.prefill_attention(q, k, v, causal=causal,
                                    block_q=128, block_k=256)
        want = ref.flash_prefill_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_causal_first_token_attends_itself_only(self):
        B, H, S, D = 1, 2, 256, 64
        q = jax.random.normal(jax.random.key(0), (B, H, S, D), jnp.float32)
        k = jax.random.normal(jax.random.key(1), (B, H, S, D), jnp.float32)
        v = jax.random.normal(jax.random.key(2), (B, H, S, D), jnp.float32)
        got = ops.prefill_attention(q, k, v, causal=True, block_q=128, block_k=128)
        np.testing.assert_allclose(np.asarray(got[:, :, 0]), np.asarray(v[:, :, 0]),
                                   rtol=1e-5, atol=1e-5)
