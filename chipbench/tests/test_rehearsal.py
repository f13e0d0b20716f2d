"""Every cell end to end on the CPU at the small test widths (interpret-mode
Pallas, the look for a chip skipped, a four-chip cell on four host
devices), the shape of the last line, a cell found by file name alone, and
the refusals: no TPU, or no program beside the benchmark."""

import json
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench.tests import rehearse

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CHIPS = {w["name"]: w["chips"] for w in MANIFEST["workloads"]}
CELLS = list(CHIPS)


def e2e_names(cell):
    return {m["name"] for m in MANIFEST["end_to_end"]
            if cell in m.get("workloads", [cell])}


def layer_names(cell):
    return {m["name"] for m in MANIFEST["per_layer"] if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_prints_its_metrics(cell):
    rc, last, err = rehearse.rehearse(cell, seconds=2, devices=CHIPS[cell])
    assert rc == 0, err[-3000:]
    assert set(last) == {"correct", "attempted", "failed", "metrics", "device", "checks"}
    assert list(last)[-1] == "checks"
    assert last["correct"] is True, last["checks"]
    assert set(last["metrics"]) == e2e_names(cell)
    assert last["checks"]["window_compiles"]["value"] == 0
    assert "check window_compiles: 0" in err


@pytest.mark.parametrize("cell", ["yi6b-train-divebatch", "yi6b-serve-rag", "yi6b-train-fsdp4"])
def test_traced_run_reports_per_layer_metrics(cell):
    rc, last, err = rehearse.rehearse(cell, seconds=2, trace=1, devices=CHIPS[cell])
    assert rc == 0, err[-3000:]
    assert set(last["metrics"]) <= layer_names(cell)
    assert last["metrics"], last
    assert last["device"]["busy_s"] > 0 and last["device"]["window_s"] > 0
    assert len(last["breakdown"]["device_ops"]) <= 10
    assert len(last["breakdown"]["idle_gaps"]) <= 10


def test_four_chip_cell_trains_sharded_over_four_devices():
    """dp = fsdp = 4: correct, nothing compiled in the window, and the
    schedule 4x4 8x4 16x4 then 32 sequences."""
    rc, last, err = rehearse.rehearse("yi6b-train-fsdp4", seconds=3, devices=4)
    assert rc == 0, err[-3000:]
    assert last["correct"] is True, last["checks"]
    assert last["device"]["count"] == 4
    assert last["checks"]["window_compiles"]["value"] == 0
    schedule = re.search(r"^schedule (.*)$", err, re.M).group(1)
    assert re.fullmatch(r"4x4 8x4 16x4 32x\d+", schedule), schedule


def test_a_dropped_in_cell_file_is_picked_up(tmp_path):
    """A new cell is new files and a manifest entry: no code changes."""
    rehearse.scratch_copy(tmp_path)
    bench = tmp_path / "chipbench"
    shutil.copy(bench / "workloads" / "yi6b-serve-chat.json",
                bench / "workloads" / "yi6b-serve-chat-short.json")
    mix = json.loads((bench / "traffic" / "chat.json").read_text())
    mix["output"] = {"min": 2, "max": 4}
    (bench / "traffic" / "chat-short.json").write_text(json.dumps(mix))
    manifest = json.loads((tmp_path / "BENCHMARK.json").read_text())
    manifest["workloads"].append({"name": "yi6b-serve-chat-short", "config": "yi6b-serve",
                                  "traffic": "chat-short", "chips": 1, "why": "test"})
    for m in manifest["end_to_end"]:
        if "yi6b-serve-chat" in m.get("workloads", []):
            m["workloads"].append("yi6b-serve-chat-short")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    rc, last, err = rehearse.rehearse("yi6b-serve-chat-short", seconds=1, dest=tmp_path)
    assert rc == 0, err[-3000:]
    assert last["correct"] is True
    assert "serve_tokens_per_s" in last["metrics"]


def test_no_tpu_exits_nonzero_without_a_result():
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload", "yi6b-serve-chat",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, capture_output=True, text=True, timeout=300,
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_alone_exits_nonzero(tmp_path):
    """A directory with BENCHMARK.json and chipbench/ only has no program to run."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload", "yi6b-serve-chat",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, capture_output=True, text=True, timeout=300,
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
