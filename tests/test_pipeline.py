"""Pipeline parallelism: GPipe schedule == sequential oracle, on a real
4-device stage mesh (subprocess)."""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.parallel.pipeline import pipeline_forward, reference_forward

    assert len(jax.devices()) == 4
    from jax.sharding import AxisType
    mesh = jax.make_mesh((4,), ("stage",), axis_types=(AxisType.Auto,))

    D = 16
    def stage_fn(p, x):          # shape-preserving block
        return jnp.tanh(x @ p["w"] + p["b"])

    key = jax.random.key(0)
    params = {
        "w": jax.random.normal(key, (4, D, D)) * 0.5,
        "b": jnp.zeros((4, D)),
    }
    batch = jax.random.normal(jax.random.fold_in(key, 1), (6, 8, D))  # 6 micro

    got = pipeline_forward(stage_fn, params, batch, mesh)
    want = reference_forward(stage_fn, params, batch)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    print("PIPELINE_OK")
""")


@pytest.mark.slow
def test_gpipe_matches_sequential():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert "PIPELINE_OK" in r.stdout
