"""GPipe pipeline-parallel tests.

The multi-stage case needs >1 device, and jax pins the device count at first
init — so the real pipeline run happens in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=4.
"""

import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.pipeline import bubble_fraction, gpipe_forward


def test_bubble_fraction():
    assert bubble_fraction(8, 4) == 3 / 11
    assert bubble_fraction(1, 1) == 0.0


def test_gpipe_single_stage_degenerate():
    """pipe=1 == plain scan over layers."""
    mesh = jax.make_mesh((1,), ("pipe",))
    l, d, m, b = 4, 8, 3, 2
    w = jax.random.normal(jax.random.key(0), (l, d, d)) * 0.3
    x = jax.random.normal(jax.random.key(1), (m, b, d))

    def block(wl, h):
        return jnp.tanh(h @ wl)

    out = gpipe_forward(block, w, x, mesh)
    ref = x
    for i in range(l):
        ref = jnp.tanh(ref @ w[i])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


SUBPROCESS_PROG = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"  # skip TPU probing in the bare env
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.models.pipeline import gpipe_forward

    mesh = jax.make_mesh((4,), ("pipe",))
    l, d, m, b = 8, 16, 6, 2
    w = jax.random.normal(jax.random.key(0), (l, d, d)) * 0.3
    x = jax.random.normal(jax.random.key(1), (m, b, d))

    def block(wl, h):
        return jnp.tanh(h @ wl)

    out = gpipe_forward(block, w, x, mesh)
    ref = x
    for i in range(l):
        ref = jnp.tanh(ref @ w[i])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    print("PIPELINE_OK")
""")


def test_gpipe_four_stages_subprocess():
    res = subprocess.run(
        [sys.executable, "-c", SUBPROCESS_PROG],
        capture_output=True, text=True, timeout=300,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"},
        cwd=Path(__file__).resolve().parent.parent,
    )
    assert "PIPELINE_OK" in res.stdout, f"stdout={res.stdout}\nstderr={res.stderr[-2000:]}"
