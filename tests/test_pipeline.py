"""GPipe pipeline-parallel runner test (needs >1 device: subprocess with
forced host device count, same pattern as the dry-run)."""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.nn.pipeline import pipeline_apply

from jax.sharding import AxisType
mesh = jax.make_mesh((4, 2), ("pod", "data"),
                     axis_types=(AxisType.Auto,) * 2)
S, M, mb, d = 4, 6, 2, 8
params = jnp.arange(1.0, S + 1)[:, None] * jnp.ones((S, d))
x = jnp.asarray(np.random.default_rng(0).standard_normal((M, mb, d)),
                jnp.float32)

def stage(p, x):
    return x + p[None, :]

out = jax.jit(lambda p, x: pipeline_apply(mesh, "pod", stage, p, x))(params, x)
np.testing.assert_allclose(np.asarray(out), np.asarray(x + 10.0), atol=1e-6)
g = jax.grad(lambda p: jnp.sum(pipeline_apply(mesh, "pod", stage, p, x)**2))(
    params
)
assert np.isfinite(np.asarray(g)).all()
print("PIPELINE_TEST_PASS")
"""


@pytest.mark.timeout(300)
def test_pipeline_parallel_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True,
        text=True, timeout=280,
    )
    assert "PIPELINE_TEST_PASS" in out.stdout, out.stderr[-2000:]
