"""With the timed path broken underneath the harness, ``correct`` comes out
false: once for each fault a cell can have (one chip: no exchange between
chips to leave out)."""

import pytest

from chipbench.tests import rehearse


@pytest.mark.parametrize("cell,fault", [
    ("yi6b-train-divebatch", "unchanged"),
    ("yi6b-train-divebatch", "half_batch"),
    ("yi6b-serve-chat", "token"),
    ("yi6b-serve-rag", "token"),
])
def test_fault_is_not_correct(cell, fault):
    rc, last, err = rehearse.rehearse(cell, seconds=1, fault=fault)
    assert rc == 0, err[-3000:]
    assert last["correct"] is False, last["checks"]
