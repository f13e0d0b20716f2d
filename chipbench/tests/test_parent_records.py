"""The one-chip cells read as they did before configurations named their own
reference (chipbench/tests/data/parent_records.json holds the readings of the
benchmark before that change, on the CPU at the small test widths): the
train cell's compared numbers and schedule in a rehearsal, and, for every
configuration, the weights the drivers make and the serving comparison on a
fixed set of served requests."""

import hashlib
import json
import math
import re
from pathlib import Path

import numpy as np
import pytest

from chipbench import harness
from chipbench.tests import rehearse

BENCH = Path(__file__).resolve().parents[1]
RECORDS = json.loads((Path(__file__).resolve().parent / "data" / "parent_records.json").read_text())
SEED = 3_000_000_017


def small_cell(workload: str, config: str, seed: int = SEED) -> harness.Cell:
    """A cell at the rehearsal's small widths, built in this process."""
    c = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    c.update(rehearse.SMALL)
    c["program"].update(rehearse.SMALL_PROGRAM)
    if "engine" in c:
        c["engine"].update(rehearse.SMALL_ENGINE)
    spec = json.loads((BENCH / "workloads" / f"{workload}.json").read_text())
    return harness.Cell(name=workload, chips=1, config=c, traffic={}, spec=spec, end_to_end=[],
                        per_layer=[], seed=seed)


def weight_digest(weights) -> str:
    """SHA-256 of every leaf's bytes, in tree order."""
    import jax

    h = hashlib.sha256()
    for x in jax.tree.leaves(weights):
        h.update(np.asarray(x).tobytes())
    return h.hexdigest()


def served_set(vocab: int, block: int, seed: int = 7) -> dict:
    """Eight made-up finished requests: prompts of 16-64 tokens, 4-12 served."""
    rng = np.random.default_rng(seed)
    out = {}
    for rid in range(8):
        prompt = rng.integers(1, vocab, size=int(rng.integers(16, 65)), dtype=np.int32)
        out[rid] = (prompt, rng.integers(0, vocab, size=int(rng.integers(4, 13)), dtype=np.int32))
    return out


def test_train_rehearsal_reads_as_before():
    rc, last, err = rehearse.rehearse("yi6b-train-divebatch", seconds=2, seed=SEED)
    assert rc == 0, err[-3000:]
    want = RECORDS["yi6b-train-divebatch"]
    for name, value in want["checks"].items():
        assert math.isclose(last["checks"][name]["value"], value, rel_tol=1e-6, abs_tol=1e-12), name
    schedule = re.search(r"^schedule (.*)$", err, re.M).group(1)
    assert schedule.startswith(want["schedule_prefix"]), schedule


@pytest.mark.parametrize("workload,config", [("yi6b-train-divebatch", "yi6b-train-2l"),
                                             ("yi6b-serve-chat", "yi6b-serve")])
def test_weights_are_as_before(workload, config):
    cell = small_cell(workload, config)
    ref = harness.load_reference(cell)
    dims = ref.dims_of(cell.config)
    got = weight_digest(ref.make_weights(dims, harness.key_of(SEED),
                                         cell.config["program"]["param_dtype"]))
    assert got == RECORDS["weights"][config]


def test_train_state_holds_the_weights_as_before():
    """The train driver makes its state in one jitted call from the seed's
    key: its parameters are the reference's weights, bit for bit."""
    cell = small_cell("yi6b-train-divebatch", "yi6b-train-2l")
    job = json.loads((BENCH / "traffic" / "divebatch.json").read_text())
    job.update(job.pop("test", {}))
    cell.traffic = job
    _, _, _, state = harness.load_driver("train").build(cell)
    assert weight_digest(state.params) == RECORDS["weights"]["yi6b-train-2l"]


@pytest.mark.parametrize("workload", ["yi6b-serve-chat", "yi6b-serve-rag"])
def test_serving_comparison_is_as_before(workload):
    cell = small_cell(workload, "yi6b-serve")
    driver = harness.load_driver("serve")
    ref = harness.load_reference(cell)
    dims = ref.dims_of(cell.config)
    block = cell.config["engine"]["block"]
    spec = dict(cell.spec, check_span=256)
    got = driver.check(cell, ref, dims, served_set(dims["vocab_size"], block), block, spec,
                       control=True)
    want = RECORDS[workload]
    assert got["checked_tokens"] == want["checked_tokens"]
    for name in ("served_token_gap", "control_gap"):
        assert math.isclose(got[name], want[name], rel_tol=1e-6), name
