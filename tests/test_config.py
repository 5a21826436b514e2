"""Process-level configuration: the backend-derived interpret default, the
TPU kernel capability table, and the platform helpers that must fail
loudly instead of degrading (device count, peak table, compile cache)."""
import jax
import numpy as np
import pytest

from repro import config
from repro.core import Atom, Database, JoinQuery, build_shred, probe, sampling


def _shred_p():
    rng = np.random.default_rng(0)
    db = Database.from_columns({
        "R": {"x": rng.integers(0, 5, 30), "y": rng.integers(0, 5, 30),
              "p": rng.random(30)},
        "S": {"y": rng.integers(0, 5, 20), "z": rng.integers(0, 5, 20)},
    })
    q = JoinQuery((Atom.of("R", "x", "y", "p"), Atom.of("S", "y", "z")),
                  prob_var="p")
    shred = build_shred(db, q)
    return shred, shred.root.data.column("p")


@pytest.fixture
def on_tpu(monkeypatch):
    """Route decisions as a TPU process makes them (nothing is compiled)."""
    monkeypatch.setattr(config, "backend", lambda: "tpu")


class TestInterpretDefault:
    def test_backend_decides(self, monkeypatch):
        monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
        assert config.KernelPolicy().interpret          # CPU: interpreter
        assert config.current_policy().interpret
        monkeypatch.setattr(config, "backend", lambda: "tpu")
        assert not config.KernelPolicy().interpret      # TPU: compiled
        assert not config.current_policy().interpret
        assert config.current_policy().preferred

    def test_env_overrides_backend(self, on_tpu, monkeypatch):
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
        assert config.current_policy().interpret
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "")
        assert not config.current_policy().interpret
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
        assert not config.current_policy().interpret

    def test_resolve_interpret(self, monkeypatch):
        monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
        assert config.resolve_interpret(None) is True
        assert config.resolve_interpret(False) is False


class TestTpuCapability:
    def test_interpreter_runs_every_kernel(self, on_tpu):
        pol = config.KernelPolicy(interpret=True)
        assert all(config.kernel_unsupported(k, pol) is None
                   for k in config.TPU_UNLOWERABLE)

    def test_compiled_tpu_declares_reasons(self, on_tpu):
        pol = config.KernelPolicy(interpret=False)
        for k, (message, _) in config.TPU_UNLOWERABLE.items():
            assert message in config.kernel_unsupported(k, pol)
        assert config.kernel_unsupported("bsearch_probe", pol) is None

    def test_auto_routes_leave_unlowerable_kernels(self, on_tpu):
        shred, p = _shred_p()
        assert shred.packed is not None
        dp = sampling.fused_draw_params(shred.root.weight, p,
                                        shred.root_prefE)
        pol = config.KernelPolicy(interpret=False)
        assert probe.select_rep(shred, "usr", pol) == "usr"
        assert probe.select_draw(shred, dp, method="exprace",
                                 policy=pol) == "pernode"

    def test_explicit_requests_raise_with_reason(self, on_tpu):
        shred, p = _shred_p()
        dp = sampling.fused_draw_params(shred.root.weight, p,
                                        shred.root_prefE)
        pol = config.KernelPolicy(interpret=False)
        with pytest.raises(NotImplementedError, match="cumsum"):
            probe.select_draw(shred, dp, method="exprace", kernels="fused",
                              policy=pol)
        with pytest.raises(NotImplementedError, match="cumsum"):
            probe.select_draw(shred, dp, method="exprace", kernels="paged",
                              policy=pol)
        with config.override(pol):
            with pytest.raises(NotImplementedError, match="2D gather"):
                probe.get_rows(shred, jax.numpy.arange(4), rep="usr_fused")
            with pytest.raises(NotImplementedError, match="2D gather"):
                probe.get_rows(shred, jax.numpy.arange(4), rep="usr_paged")
        # The reference twin is plain XLA: still available on the TPU.
        assert probe.select_draw(shred, dp, method="exprace",
                                 kernels="reference",
                                 policy=pol) == "reference"


class TestPlatformHelpers:
    def test_force_host_devices_raises_when_short(self, monkeypatch):
        monkeypatch.setenv("XLA_FLAGS", "")
        have = len(jax.devices())
        assert config.force_host_devices(have) == have
        with pytest.raises(RuntimeError, match="requested"):
            config.force_host_devices(have + 64)

    def test_peak_table_is_keyed_by_device_kind(self):
        assert config.peak_flops("TPU v5 lite") == 197e12
        assert config.PEAK_FLOPS["TPU v5 lite"]["source"]
        with pytest.raises(KeyError, match="no published peak"):
            config.peak_flops("cpu")

    def test_compile_cache_dir(self, monkeypatch):
        prev = jax.config.jax_compilation_cache_dir
        try:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
            jax.config.update("jax_compilation_cache_dir", prev)
            assert config.enable_compile_cache() == "/elsewhere"
            assert jax.config.jax_compilation_cache_dir == prev
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
            assert config.enable_compile_cache() == config.COMPILE_CACHE_DIR
            assert jax.config.jax_compilation_cache_dir == \
                config.COMPILE_CACHE_DIR
            assert config.COMPILE_CACHE_DIR.endswith(".jax_cache")
        finally:
            jax.config.update("jax_compilation_cache_dir", prev)
