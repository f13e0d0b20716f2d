"""Mini dry-run: the full lower+compile pipeline on an 8-device host mesh
(subprocess, since device count locks at first jax init). Exercises exactly
the code paths of the 512-chip production dry-run at test-friendly scale."""

import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config, SHAPES, input_specs
from repro.configs.base import ShapeConfig
from repro.dist import sharding as shd
from repro.dist.plan import ShardingPlan, make_mesh, use_plan
from repro.models import transformer as tf
from repro.optim import sgd
from repro.train.state import init_state
from repro.train.engine import StepEngine
from repro.utils import hlo as hlo_lib

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
plan = ShardingPlan(mesh=mesh, dp=("pod", "data"), fsdp=("pod", "data"),
                    tp="model", ep=("pod", "data"))

for arch in ["qwen2-7b", "kimi-k2-1t-a32b", "jamba-v0.1-52b"]:
    cfg = get_config(arch, reduced=True).replace(scan_layers=True, remat=True)
    shape = ShapeConfig("mini_train", "train", 64, 16)
    opt = sgd(momentum=0.9)
    params_specs = tf.param_specs(cfg)
    state_specs = jax.eval_shape(lambda p: init_state(p, opt), params_specs)
    state_sh = shd.shardings_of(shd.infer_pspecs(state_specs, plan), plan)
    batch_specs = input_specs(cfg, shape)["batch"]
    batch_sh = shd.shardings_of(shd.batch_pspecs(batch_specs, plan), plan)
    # same engine path as launch/dryrun.py::build_train
    engine = StepEngine.for_lm(cfg, opt, dp_size=plan.dp_size,
                               moe_groups=plan.dp_size if cfg.num_experts else 1,
                               in_shardings=(state_sh, batch_sh, None),
                               out_shardings=(state_sh, None))
    with use_plan(plan, {"residual": P(("pod", "data"), None, "model")}):
        with mesh:
            lowered = engine.jitted(2).lower(
                state_specs, batch_specs, jax.ShapeDtypeStruct((), jnp.float32))
            compiled = lowered.compile()
    mem = compiled.memory_analysis()
    analysis = hlo_lib.analyze_hlo(compiled.as_text())
    assert analysis["flops"] > 0, arch
    assert mem.temp_size_in_bytes > 0, arch
    print("OK", arch, analysis["flops"], analysis["collectives"]["total_operand_bytes"])

# decode path on one arch
cfg = get_config("yi-6b", reduced=True)
cache_specs = tf.cache_specs(cfg, 16, 64)
cache_sh = shd.shardings_of(shd.cache_pspecs(cache_specs, plan), plan)
params_specs = tf.param_specs(cfg)
params_sh = shd.shardings_of(shd.infer_pspecs(params_specs, plan), plan)
tok = jax.ShapeDtypeStruct((16, 1), jnp.int32)
tok_sh = NamedSharding(mesh, P(("pod", "data"), None))
with mesh:
    compiled = jax.jit(lambda p, c, t: tf.decode_step(cfg, p, c, t),
                       in_shardings=(params_sh, cache_sh, tok_sh),
                       out_shardings=(None, cache_sh)).lower(
        params_specs, cache_specs, tok).compile()
print("OK decode", compiled.memory_analysis().temp_size_in_bytes)
"""


@pytest.mark.slow
def test_mini_dryrun_compiles():
    res = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": "src"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    assert res.stdout.count("OK") == 4, res.stdout
