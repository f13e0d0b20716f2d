"""Run a cell end to end on the CPU at the small test widths, in a scratch
copy of the benchmark: the look for a chip is skipped (the CPU stands in,
with made-up peaks) and Pallas runs in interpret mode.

    python chipbench/tests/rehearse.py <cell> [--seconds S] [--trace 0|1]
        [--fault NAME] [--devices N]

Cell files are rewritten in the copy only: each configuration takes the
small widths below, each traffic mix its ``test`` overrides.  ``--fault``
breaks the timed path underneath the harness (see FAULTS) to show that
``correct`` comes out false.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

# yi_6b.reduced(): the repository's own small Yi-6B, float32 throughout
SMALL = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
         "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 251}
SMALL_PROGRAM = {"num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
                 "d_ff": 128, "vocab_size": 251, "xent_chunk": 64,
                 "param_dtype": "float32", "compute_dtype": "float32"}
SMALL_ENGINE = {"slots": 4, "max_seq": 256, "block": 16, "prefill_chunk": 32, "pool_blocks": 64}

CHILD = r"""
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
import jax
from chipbench import harness, run
fault = {fault!r}
if {trace!r}:
    from chipbench.tests import fixture_trace
    fixture_trace.stand_in()
if fault:
    from chipbench.tests import faults
    faults.plant(fault)
peak = {{"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}}
main = run.main
if {calibrate!r}:
    from chipbench import calibrate
    main = calibrate.main
sys.exit(main({argv!r}, require_devices=lambda n: (jax.devices()[:n], peak)))
"""


def scratch_copy(dest: Path) -> Path:
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", dest / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for path in (dest / "chipbench" / "configs").glob("*.json"):
        c = json.loads(path.read_text())
        c.update(SMALL)
        c["program"].update(SMALL_PROGRAM)
        if "engine" in c:
            c["engine"].update(SMALL_ENGINE)
        path.write_text(json.dumps(c))
    for path in (dest / "chipbench" / "traffic").glob("*.json"):
        t = json.loads(path.read_text())
        t.update(t.pop("test", {}))
        path.write_text(json.dumps(t))
    return dest


def rehearse(cell: str, *, seconds: float = 2.0, trace: int = 0, fault: str = "",
             devices: int = 1, dest: Path | None = None, seed: int = 3_000_000_017,
             calibrate: str = "", args=()):
    """Run the cell in a scratch copy; returns (exit code, last stdout line
    as a dict or None, stderr).  ``calibrate="<seeds>;<control seeds>"`` runs
    chipbench/calibrate.py instead, with ``args`` added to its arguments,
    and the whole stdout comes back."""
    own = dest is None
    dest = Path(tempfile.mkdtemp()) if own else dest
    try:
        if not (dest / "chipbench").exists():
            scratch_copy(dest)
        argv = ["--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
                "--trace", str(trace)]
        if calibrate:
            seeds, controls = calibrate.split(";")
            argv = ["--workload", cell, "--seeds", seeds, "--control-seeds", controls,
                    "--seconds", str(seconds), *args]
        code = CHILD.format(root=str(dest), src=str(ROOT / "src"), fault=fault, argv=argv,
                            trace=trace, calibrate=bool(calibrate))
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
        p = subprocess.run([sys.executable, "-c", code], cwd=dest, env=env,
                           capture_output=True, text=True, timeout=900)
        lines = [x for x in p.stdout.splitlines() if x.strip()]
        if calibrate:
            return p.returncode, [json.loads(x) for x in lines if x.startswith("{")], p.stderr
        last = json.loads(lines[-1]) if lines and lines[-1].startswith("{") else None
        return p.returncode, last, p.stderr
    finally:
        if own:
            shutil.rmtree(dest, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--fault", default="")
    ap.add_argument("--devices", type=int, default=1)
    a = ap.parse_args()
    rc, last, err = rehearse(a.cell, seconds=a.seconds, trace=a.trace, fault=a.fault,
                             devices=a.devices)
    print(err[-4000:], file=sys.stderr)
    print(json.dumps(last))
    return rc


if __name__ == "__main__":
    sys.exit(main())
