"""chip_smoke.py rehearsed on the CPU: its phase functions at
``yi_6b.reduced()`` size, with the Pallas kernels in interpret mode, and its
refusal to run anywhere but on a TPU."""

import importlib.util
import os
import shutil
import subprocess
import sys

import jax
import pytest

from repro.configs import yi_6b

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


TRAIN = dict(seq_len=64, micro_batch=2, m_max=4, steps=6, tick_every=2, lr=0.02)
SERVE = dict(num_layers=2, prompt_lens=(8, 12, 16, 24, 32, 40, 64),
             follower=(4, 24), max_new=4, block=8, chunk=16, max_seq=128,
             pool_blocks=64, checked=(0, 7))


def test_main_refuses_a_cpu_backend(smoke, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == ""  # no phase ran, no result line
    assert "no TPU" in out.err


@pytest.mark.parametrize("alone", [False, True], ids=["in-repo", "script-alone"])
def test_script_exits_nonzero_without_a_chip(tmp_path, alone):
    """Run as a script: from the repo root on a CPU backend, and as a lone
    copy with nothing else of the repo beside it."""
    script = SCRIPT
    if alone:
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SCRIPT, script)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, script], capture_output=True, text=True,
                         cwd=os.path.dirname(script), env=env, timeout=120)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout


def test_train_phase_resizes_and_matches_reference(smoke):
    out = smoke.train_phase(yi_6b.reduced(), log=lambda s: None, **TRAIN)
    assert out["schedule"][0] == TRAIN["micro_batch"]
    assert out["schedule"][-1] == TRAIN["m_max"]
    assert sorted(set(out["buckets"])) == [1, 2]
    assert all(t["diversity"] > 0 and t["gns"] > 0 for t in out["ticks"])


def test_serve_phase_answers_and_matches_full_forward(smoke):
    out = smoke.serve_phase(yi_6b.reduced(), log=lambda s: None, **SERVE)
    assert len(out["tokens"]) == len(SERVE["prompt_lens"]) + 1
    # float32 here: the paged path agrees with the full forward far inside
    # the bf16 tolerance the chip run is held to
    assert max(out["errs"]) < 1e-3
    assert max(out["gaps"]) < 1e-3


def test_four_chip_phase_on_host_devices(smoke):
    out = smoke.four_chip_phase(
        yi_6b.reduced(), jax.devices()[:4], log=lambda s: None,
        train=dict(TRAIN, num_layers=2, micro_batch=4, m_max=8),
        serve=SERVE,
    )
    assert out["train_one"]["schedule"] == out["train_four"]["schedule"]
    assert out["serve_one"]["tokens"] == out["serve_elastic"]["tokens"]
