"""shard_map EP MoE vs the dense oracle, on an 8-device mesh (subprocess)."""

import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.models import moe as moe_lib
from repro.dist.plan import make_mesh
from repro.models.moe_ep import moe_apply_ep

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
key = jax.random.key(0)
d, ff, E, topk = 32, 64, 8, 2
params = moe_lib.moe_init(key, d, ff, E)
x = jax.random.normal(jax.random.key(1), (4, 16, d))

def ep(x):
    return moe_apply_ep(params, x, top_k=topk, capacity_factor=8.0, act="silu",
                        mesh=mesh, dp_axes=("pod", "data"),
                        ep_axes=("pod", "data"), tp_axis="model")

with mesh:
    y_ep, aux = jax.jit(ep)(x)
y_ref = moe_lib.moe_reference(params, x, top_k=topk)
err = float(jnp.abs(y_ep - y_ref).max())
assert err < 2e-5, err
print("OK forward", err)

# gradients flow end to end
def loss(p, x):
    y, aux = moe_apply_ep(p, x, top_k=topk, capacity_factor=8.0, act="silu",
                          mesh=mesh, dp_axes=("pod", "data"),
                          ep_axes=("pod", "data"), tp_axis="model")
    return (y ** 2).sum() + 0.01 * aux

with mesh:
    g = jax.jit(jax.grad(loss))(params, x)
def loss_ref(p, x):
    y = moe_lib.moe_reference(p, x, top_k=topk)
    # reference aux identical formulation
    return (y ** 2).sum()
g_ref = jax.grad(loss_ref)(params, x)
for ka in ("w_gate", "w_up", "w_out"):
    e = float(jnp.abs(g[ka] - g_ref[ka]).max()) / (float(jnp.abs(g_ref[ka]).max()) + 1e-9)
    assert e < 5e-4, (ka, e)
print("OK grads")
"""


@pytest.mark.slow
def test_moe_ep_matches_reference():
    res = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": "src"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    assert res.stdout.count("OK") == 2, res.stdout
