"""boost_attempt_sharded ≡ run_boost_attempt on a real 2-device mesh.

The device count must be fixed before jax initialises, so the actual
comparison runs in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=2`` (the same
pattern launch/dryrun.py uses).  Asserts identical hypotheses and
stuck verdicts for both the center and the §2.2 no-center model.
"""

import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import numpy as np
import jax
import jax.numpy as jnp

from repro.core import boost_attempt, tasks, weak
from repro.core.types import BoostConfig
from jax.sharding import AxisType

assert jax.device_count() == 2, jax.devices()

N = 1 << 12
cls = weak.Thresholds(n=N)
k, m = 2, 1024
cfg = BoostConfig(k=k, coreset_size=200, domain_size=N)
T = cfg.num_rounds(m)

for noise, seed in ((0, 5), (3, 8)):
    task = tasks.make_task(cls, m=m, k=k, noise=noise, seed=seed)
    xk = jnp.asarray(task.x)          # [2, m/2] — one shard per device
    yk = jnp.asarray(task.y)
    ref = boost_attempt.run_boost_attempt(
        xk, yk, jnp.ones_like(xk, bool), jax.random.key(0), cfg, cls)

    mesh = jax.make_mesh((2,), ("data",), axis_types=(AxisType.Auto,))
    x = xk.reshape(-1)
    y = yk.reshape(-1)
    args = (x, y, jnp.ones_like(x, bool), jnp.zeros_like(x),
            jax.random.key(0))
    for no_center in (False, True):
        fn = boost_attempt.boost_attempt_sharded(
            mesh, cfg, cls, num_rounds=T, no_center=no_center)
        t, stuck, hits, h_params, loss = fn(*args)
        assert bool(stuck) == ref.stuck, (no_center, noise)
        assert int(t) == ref.rounds, (no_center, noise, int(t), ref.rounds)
        np.testing.assert_array_equal(
            np.asarray(h_params)[:int(t)],
            np.asarray(ref.hypotheses)[:ref.rounds],
            err_msg=f"no_center={no_center} noise={noise}")
        if not ref.stuck:
            g = weak.ensemble_predict(cls, h_params, int(t), x)
            assert int(weak.empirical_errors(g, y)) == 0
print("SHARDED_PARITY_OK")
"""


@pytest.mark.xdist_group(name="device_mesh_subprocess")
def test_sharded_parity_two_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=2")
    env["JAX_PLATFORMS"] = "cpu"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = (os.path.join(root, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "SHARDED_PARITY_OK" in out.stdout
