#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell, in one
process (the benchmark's own runs never do this).

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 [--seconds S] [--fault NAME[,NAME...]]

For each seed it prints one JSON line with the numbers the cell compares,
the program against the plain reference.  On the control seeds it adds the
control: the reference computed with float8 matmul operands in the
program's place.  A training cell also reads the half-batch fault there
(the reference's steps over the first half of each batch's rows, the mean
taken over them) and, on more than one chip, the lost-shard fault (the rows
the last device holds add nothing to the gradient, whose mean is still
taken over all rows); a state left unchanged reads 1 by the measure and
needs no run.  A serving cell runs a
window of ``--seconds`` at its own load per seed.  ``--fault`` plants one of
``chipbench/tests/faults.py`` in the program first; its readings are then
labelled with the fault's name.  A training cell takes a comma-separated
list there: each fault is planted in turn while the program runs its first
steps, and the reference runs once a seed for all of them.  Each training
line gives the seconds the program's steps (``program_s``, set-up to the
last of the three) and the reference (``reference_s``) took.  The last line
sums up: per number, the largest program reading and the smallest control
and fault readings.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def program_steps(cell, driver):
    """The program's steps 1-3 from the seed, its state freed after them."""
    plan = driver.plan_of(cell.devices)
    with driver.under(plan):
        _, pool, engine, state = driver.build(cell, plan)
        feed = driver.Feed(pool, cell.devices, plan)
        state, metrics, prog, rows, lr0 = driver.first_steps(cell, engine, state, feed)
    del state, engine, metrics
    gc.collect()
    return prog, rows, lr0


def train_readings(cell, driver, seed: int, controls: bool, planted=("",)) -> list[dict]:
    """``planted`` names the faults to plant in turn, "" for the program as
    it is."""
    from chipbench.tests import faults

    cell.seed = seed
    progs, took = {}, {}
    for name in planted:
        undo = faults.plant(name) if name else None
        t = time.perf_counter()
        try:
            progs[name or "program"], rows, lr0 = program_steps(cell, driver)
        finally:
            if undo:
                undo()
        took[name or "program"] = time.perf_counter() - t
    t = time.perf_counter()
    ref = driver.reference(cell, rows, lr0)
    ref_s = time.perf_counter() - t
    out = [dict(seed=seed, kind=kind, **driver.compare(prog, ref),
                losses=prog["losses"], ref_losses=ref["losses"],
                diversity=prog["diversity"], ref_diversity=ref["diversity"],
                program_s=took[kind], reference_s=ref_s) for kind, prog in progs.items()]
    if controls:
        ctl = driver.reference(cell, rows, lr0, "fp8")
        out.append(dict(seed=seed, kind="control", **driver.compare(ctl, ref)))
        half = driver.reference(cell, rows, lr0, "f32", keep_rows=len(rows[0]["tokens"]) // 2)
        out.append(dict(seed=seed, kind="half_batch_reference", **driver.compare(half, ref)))
        if len(cell.devices) > 1:
            lost = driver.reference(cell, rows, lr0, "f32", lost=driver.lost_rows(cell))
            out.append(dict(seed=seed, kind="lost_shard_reference", **driver.compare(lost, ref)))
    return out


def serve_readings(cell, driver, seed: int, controls: bool, planted=("",)) -> list[dict]:
    cell.seed = seed
    rec = driver.run(cell, time.perf_counter(), control=controls)
    got = rec["checked"]
    out = [dict(seed=seed, kind=planted[0] or "program",
                served_token_gap=got["served_token_gap"], checked_tokens=got["checked_tokens"], finished=rec["finished"],
                window_compiles=rec["window_compiles"])]
    if controls:
        out.append(dict(seed=seed, kind="control", served_token_gap=got["control_gap"]))
    gc.collect()
    return out


def main(argv=None, *, require_devices=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--fault", default="")
    args = ap.parse_args(argv)
    planted = args.fault.split(",")
    from chipbench import harness

    cell = harness.load_cell(args.workload)
    if cell.spec["driver"] != "train" and args.fault:
        if len(planted) > 1:
            ap.error("a serving cell takes one fault")
        from chipbench.tests import faults

        faults.plant(args.fault)
    try:
        cell.devices, cell.peak = (require_devices or harness.require_devices)(cell.chips)
    except harness.NoDevice as e:
        print(f"chipbench: {e}; no run", file=sys.stderr)
        return 2
    cell.seconds = args.seconds
    harness.enable_compile_cache()
    driver = harness.load_driver(cell.spec["driver"])
    readings = train_readings if cell.spec["driver"] == "train" else serve_readings
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        for r in readings(cell, driver, seed, seed in controls, planted):
            rows.append(r)
            print(json.dumps(r), flush=True)
    summary = {}
    for name in cell.limits:
        prog = [r[name] for r in rows if r["kind"] == "program"]
        summary[name] = {"program_max": max(prog, default=None), "program_seeds": len(prog)}
        for kind in sorted({r["kind"] for r in rows} - {"program"}):
            vals = [r[name] for r in rows if r["kind"] == kind and name in r]
            if vals:
                summary[name][f"{kind}_min"] = min(vals)
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
