"""Gradient compression: quantisation error, error feedback, multi-device
compressed reduction (8 host devices in a subprocess)."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist.compression import compress_leaf, init_error_state
from repro.dist.plan import make_mesh


def test_error_feedback_unbiased_over_time():
    """Error feedback: the ACCUMULATED transmitted signal converges to the
    accumulated true signal (residual stays bounded)."""
    rng = np.random.default_rng(0)
    g_true = jnp.asarray(rng.standard_normal((16, 64)), jnp.float32)
    err = jnp.zeros((16, 64), jnp.float32)
    sent = jnp.zeros_like(g_true)
    for _ in range(50):
        deq, err = compress_leaf(g_true, err)
        sent = sent + deq
    # average transmitted ~= g_true; residual bounded by one quant step
    avg = sent / 50
    assert float(jnp.abs(avg - g_true).max()) < 0.05
    assert float(jnp.abs(err).max()) < float(jnp.abs(g_true).max())


def test_compress_leaf_shapes():
    for shape in [(), (7,), (3, 5), (2, 3, 4)]:
        g = jnp.ones(shape, jnp.float32)
        err = jnp.zeros(shape, jnp.float32)
        deq, new_err = compress_leaf(g, err)
        assert deq.shape == shape and new_err.shape == shape


def test_make_compressed_pod_mean_keeps_per_pod_residuals():
    """Wrapper contract: mean replicated, residuals PER-POD (each pod must
    fold its own quantization error back, or error feedback is broken)."""
    from repro.dist.compression import make_compressed_pod_mean

    mesh = make_mesh((8,), ("pod",))  # conftest forces 8 host devices
    r = np.random.default_rng(1)
    g = jnp.asarray(r.standard_normal((8, 4, 16)), jnp.float32)  # stacked
    grads, err = {"w": g}, init_error_state({"w": g})
    red, new_err = jax.jit(make_compressed_pod_mean(mesh, "pod"))(grads, err)

    np.testing.assert_allclose(np.asarray(red["w"]), np.asarray(g).mean(0),
                               atol=0.05)
    ne = np.asarray(new_err["w"])
    assert ne.shape == g.shape
    # each pod's residual is its own quant error: bounded by scale/2 and
    # distinct across pods (a pod-0 broadcast would make these identical)
    for p in range(8):
        bound = np.abs(np.asarray(g[p])).max() / 254.0 + 1e-6
        assert np.abs(ne[p]).max() <= bound
    assert not np.allclose(ne[0], ne[1])


_SUBPROCESS = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.dist.compression import compressed_pod_mean, init_error_state
from repro.dist.plan import make_mesh

mesh = make_mesh((8,), ("pod",))
rng = np.random.default_rng(0)
g = jnp.asarray(rng.standard_normal((8, 32)), jnp.float32)  # per-pod grads
grads = {"w": g}
err = init_error_state({"w": g[0]})

def f(g_shard, err):
    red, new_err = compressed_pod_mean({"w": g_shard[0]}, err, "pod")
    return red["w"], new_err

fm = jax.shard_map(f, mesh=mesh, in_specs=(P("pod"), P()), out_specs=(P(), P()),
                   check_vma=False)
red, _ = jax.jit(fm)(grads["w"].reshape(8, 1, 32), err)
want = np.asarray(g).mean(0)
got = np.asarray(red)
rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
assert rel < 0.05, rel
print("OK", rel)
"""


@pytest.mark.slow
def test_multidevice_compressed_mean():
    res = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS],
        capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": "src"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=300,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    assert "OK" in res.stdout
