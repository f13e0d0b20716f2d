"""The control of each cell, the plain reference put in the program's place
and computed with float8 matmul operands, comes out not correct against the
cell's limits (at the small test widths on the CPU; at the cells' own sizes
on the chip the same code gave the readings in PERF.md)."""

import json
from pathlib import Path

import pytest

from chipbench.tests import rehearse

WORKLOADS = Path(__file__).resolve().parents[1] / "workloads"


def limits(cell):
    return json.loads((WORKLOADS / f"{cell}.json").read_text())["limits"]


@pytest.mark.parametrize("cell", ["yi6b-train-divebatch", "yi6b-serve-chat", "yi6b-serve-rag"])
def test_control_fails_a_limit(cell):
    rc, rows, err = rehearse.rehearse(cell, seconds=2, calibrate="5;5")
    assert rc == 0, err[-3000:]
    control = next(r for r in rows if r.get("kind") == "control")
    lim = limits(cell)
    assert any(control[k] > lim[k] for k in lim), (control, lim)
    program = next(r for r in rows if r.get("kind") == "program")
    assert all(program[k] <= lim[k] for k in lim), (program, lim)
