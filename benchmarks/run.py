"""Benchmark harness: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows.

Each bench runs in a process of its own, and this parent never imports
JAX: an accelerator belongs to one process at a time, and the elastic/serve
benches force a multi-device CPU harness that must not leak into the
others.  ``--one MODULE`` is the child side: it runs that bench's ``run()``
in-process and prints its rows.
"""

from __future__ import annotations

import argparse
import importlib
import subprocess
import sys

MODULES = [
    ("engine", "bench_engine"),
    ("adapt", "bench_adapt"),
    ("elastic", "bench_elastic"),
    ("serve", "bench_serve"),
    ("synthetic(fig1/2)", "bench_synthetic"),
    ("table1", "bench_table1"),
    ("table2(memory)", "bench_table2_memory"),
    ("estimators", "bench_estimators"),
    ("kernels", "bench_kernels"),
    ("roofline", "roofline"),
]


def run_one(module: str) -> None:
    mod = importlib.import_module(f"benchmarks.{module}")
    for name, us, derived in mod.run():
        print(f"{name},{us:.1f},{derived}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--one", default=None, metavar="MODULE",
                    help="run a single bench module in this process")
    args = ap.parse_args()
    if args.one:
        run_one(args.one)
        return
    print("name,us_per_call,derived", flush=True)
    failed = 0
    for label, module in MODULES:
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.run", "--one", module],
            capture_output=True, text=True,
        )
        sys.stdout.write(proc.stdout)
        if proc.returncode:
            failed += 1
            sys.stderr.write(proc.stderr)  # surface the child's actual error
            print(f"{label},0,ERROR:exit {proc.returncode}")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
