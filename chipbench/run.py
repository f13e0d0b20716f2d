#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout, on a machine with the TPU chips the cell asks
for.  Set-up (weights from the seed, warm-up of every shape the cell's
traffic reaches, all of it ``setup_s``) is followed by a window of
``--seconds`` in which nothing compiles; ``--trace 1`` also profiles a
short stretch after the window and reports the cell's per-layer metrics
instead of its end-to-end ones.  Then the outputs of the timed path are
compared with a plain reference; each compared number and its limit is
printed on standard error and in the result line.  Without a TPU, with too
few chips, or on a chip kind without known peaks, it exits 2 and prints no
result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# libtpu writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, require_devices=None) -> int:
    args = parse(argv)
    from chipbench import harness

    cell = harness.load_cell(args.workload)
    try:
        cell.devices, cell.peak = (require_devices or harness.require_devices)(cell.chips)
    except harness.NoDevice as e:
        print(f"chipbench: {e}; no run", file=sys.stderr)
        return 2
    cell.seed, cell.seconds, cell.trace = args.seed, args.seconds, bool(args.trace)
    harness.enable_compile_cache()
    driver = harness.load_driver(cell.spec["driver"])
    rec = driver.run(cell, t_start=T_START)
    line = harness.result_line(cell, rec)
    harness.print_checks(line)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
