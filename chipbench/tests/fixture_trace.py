"""The small recorded trace kept with the tests: a v5e chip running three
steps of a bf16 matmul and a Pallas flash-attention call, each step followed
by a 10 ms host wait, inside a ``chipbench.window`` span."""

from __future__ import annotations

from contextlib import contextmanager
from pathlib import Path

PATH = Path(__file__).resolve().parent / "data"


def stand_in() -> None:
    """Make the harness's profiled window yield the recorded trace's
    reduction: a CPU rehearsal of a ``--trace 1`` run has no chip to trace."""
    from chipbench import harness, trace_reduce

    @contextmanager
    def profiled(cell):
        out: dict = {}
        yield out
        out.update(trace_reduce.reduce_dir(str(PATH), window_name="chipbench.window",
                                           kernels=cell.spec.get("kernels", {}),
                                           collectives=cell.spec.get("collectives")))

    harness.profiled = profiled
