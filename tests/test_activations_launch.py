"""Activation-constraint helper + train-launcher smoke."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.activations import BATCH, MODEL, constrain, current_mesh
from repro.launch.mesh import make_smoke_mesh


def test_constrain_noop_without_mesh():
    x = jnp.ones((8, 4))
    y = constrain(x, BATCH, MODEL)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    assert current_mesh() is None


def test_constrain_under_mesh_divisible():
    mesh = make_smoke_mesh()

    def f(x):
        return constrain(x, BATCH, MODEL) * 2

    with mesh:
        out = jax.jit(f)(jnp.ones((8, 4)))
    np.testing.assert_array_equal(np.asarray(out), 2 * np.ones((8, 4)))


def test_constrain_drops_nondivisible_axes():
    """A dim that doesn't divide its axes is replicated, not an error."""
    mesh = make_smoke_mesh()

    def f(x):
        # 7 % anything==1 ok on 1x1, but the helper must also tolerate
        # axes missing from the mesh entirely
        return constrain(x, ("nonexistent",), MODEL)

    with mesh:
        out = jax.jit(f)(jnp.ones((7, 4)))
    assert out.shape == (7, 4)


@pytest.mark.slow
def test_train_launcher_smoke():
    """The end-to-end driver runs and the loss decreases (deliverable b).
    Slow-marked (a ~8 min subprocess run): CI covers it in the --run-slow
    job, keeping tier-1 under the 5-minute budget."""
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "qwen1.5-0.5b",
         "--preset", "smoke", "--steps", "12", "--batch", "4",
         "--seq", "64", "--log-every", "4"],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/tmp"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "DECREASED" in proc.stdout, proc.stdout[-2000:]
