"""The control of each cell, the plain reference put in the program's place
and computed with float8 matmul operands, comes out not correct against the
cell's limits, and so does each fault read in the reference (a training
cell's half batch, and on more than one chip its lost shard), at the small
test widths on the CPU; at the cells' own sizes on the chip the same code
gave the readings in PERF.md."""

import json
from pathlib import Path

import pytest

from chipbench.tests import rehearse

WORKLOADS = Path(__file__).resolve().parents[1] / "workloads"


def limits(cell):
    return json.loads((WORKLOADS / f"{cell}.json").read_text())["limits"]


@pytest.mark.parametrize("cell", ["yi6b-train-divebatch", "yi6b-serve-chat", "yi6b-serve-rag",
                                  "yi6b-train-fsdp4"])
def test_control_fails_a_limit(cell):
    devices = 4 if cell.endswith("fsdp4") else 1
    rc, rows, err = rehearse.rehearse(cell, seconds=2, calibrate="5;5", devices=devices)
    assert rc == 0, err[-3000:]
    lim = limits(cell)
    faults = {r.get("kind") for r in rows} - {"program", None}
    assert "control" in faults
    if devices > 1:
        assert "lost_shard_reference" in faults
    for kind in faults:  # the control and the faults read in the reference
        reading = next(r for r in rows if r.get("kind") == kind)
        assert any(reading[k] > lim[k] for k in lim), (kind, reading, lim)
    program = next(r for r in rows if r.get("kind") == "program")
    assert all(program[k] <= lim[k] for k in lim), (program, lim)
