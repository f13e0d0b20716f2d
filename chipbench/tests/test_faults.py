"""With the timed path broken underneath the harness, ``correct`` comes out
false: once for each fault a cell can have (the exchange between chips is
left out only on more than one)."""

import json
from pathlib import Path

import pytest

from chipbench.tests import rehearse

WORKLOADS = Path(__file__).resolve().parents[1] / "workloads"


@pytest.mark.parametrize("cell,fault", [
    ("yi6b-train-divebatch", "unchanged"),
    ("yi6b-train-divebatch", "half_batch"),
    ("yi6b-serve-chat", "token"),
    ("yi6b-serve-rag", "token"),
    ("yi6b-train-fsdp4", "unchanged"),
    ("yi6b-train-fsdp4", "half_batch"),
    ("yi6b-train-fsdp4", "lost_shard"),
])
def test_fault_is_not_correct(cell, fault):
    devices = 4 if cell.endswith("fsdp4") else 1
    rc, last, err = rehearse.rehearse(cell, seconds=1, fault=fault, devices=devices)
    assert rc == 0, err[-3000:]
    assert last["correct"] is False, last["checks"]


def test_faults_planted_in_turn_read_as_planted_alone():
    """calibrate.py's ``--fault`` list plants each fault in turn and takes it
    out again: the lost shard, planted after the half batch was taken out,
    reads as when it is planted alone, and the program after both reads as
    the sound program; both faults fail a limit of the cell."""
    cell = "yi6b-train-fsdp4"
    lim = json.loads((WORKLOADS / f"{cell}.json").read_text())["limits"]
    rc, rows, err = rehearse.rehearse(cell, calibrate="5;", devices=4,
                                      args=["--fault", "half_batch,lost_shard,"])
    assert rc == 0, err[-3000:]
    got = {r["kind"]: r for r in rows if "kind" in r}
    assert set(got) == {"half_batch", "lost_shard", "program"}
    for fault in ("lost_shard", ""):
        rc, alone, err = rehearse.rehearse(cell, calibrate="5;", devices=4, fault=fault)
        assert rc == 0, err[-3000:]
        want = next(r for r in alone if r.get("kind") == "program")
        assert {k: got[fault or "program"][k] for k in lim} == {k: want[k] for k in lim}
    for fault in ("half_batch", "lost_shard"):
        assert any(got[fault][k] > lim[k] for k in lim), (fault, got[fault], lim)
    assert all(got["program"][k] <= lim[k] for k in lim), (got["program"], lim)
