"""Set-up compiles nothing for a new seed that it compiled for another seed
(the second seed adds no program to the persistent compile cache):
the weights, the training state, the weights the change is measured from and
the reference's weights come from programs that take the seed's key as an
argument, so the checkout's persistent compile cache serves every later run
of a cell, whatever its seed."""

import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path

import pytest

from chipbench.tests import rehearse

CHILD = r"""
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
import jax
from chipbench import harness
harness.enable_compile_cache()
cell = harness.load_cell({cell!r})
cell.devices = jax.devices()[:cell.chips]
driver = harness.load_driver("train")
counts = []
for seed in (3_000_000_017, 3_000_000_019):
    cell.seed = seed
    plan = driver.plan_of(cell.devices)
    with driver.under(plan):
        _, pool, engine, state = driver.build(cell, plan)
        feed = driver.Feed(pool, cell.devices, plan)
        state, _, prog, rows, lr0 = driver.first_steps(cell, engine, state, feed)
    del state, engine
    driver.reference(cell, rows, lr0)
    counts.append(len(list(harness.CACHE_DIR.iterdir())))
print(json.dumps(counts))
"""


@pytest.mark.parametrize("cell,devices", [("yi6b-train-divebatch", 1), ("yi6b-train-fsdp4", 4)])
def test_a_new_seed_compiles_nothing_in_set_up(cell, devices):
    with tempfile.TemporaryDirectory() as d:
        dest = rehearse.scratch_copy(Path(d))
        code = CHILD.format(root=str(dest), src=str(rehearse.ROOT / "src"), cell=cell)
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
        p = subprocess.run([sys.executable, "-c", code], cwd=dest, env=env,
                           capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    first, second = json.loads(p.stdout.splitlines()[-1])  # programs cached after each seed
    assert first > 0
    assert second == first, (first, second)
