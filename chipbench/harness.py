"""What every cell shares: finding its files by name, the device check, the
compile cache and compile counter, the profiler window, and the result line.

A cell is found by its name in ``BENCHMARK.json``; its files are

    chipbench/workloads/<cell>.json     driver, run parameters, limits
    chipbench/configs/<config>.json     the model configuration as run
    chipbench/traffic/<traffic>.json    the traffic (or training job) mix
    chipbench/drivers/<driver>.py       ``run(cell) -> record``
    chipbench/metrics/<metric>.py       ``read(record) -> value or None``

so a later cell, configuration, traffic mix, driver or metric is a new file
and an entry in ``BENCHMARK.json``, with no edit here.

A configuration brings its own reference and counts, each a module under
``chipbench/`` named by its path from the checkout's root:

    "reference"  (required) the plain reference the drivers compare with and
                 make their weights from.  It provides
                     dims_of(config) -> dims          the sizes, as a dict
                     make_weights(dims, key, dtype)   every weight, made on the
                         device from the PRNG key ``key_of(seed)``, in the
                         tree layout the system under test loads; the drivers
                         trace it under ``jit`` with the key an argument, so
                         one compiled program serves every seed
                 and, for serving cells,
                     logits(dims, weights, tokens, precision) -> (B, S, V)
                 or, for training cells,
                     loss(dims, weights, tokens, targets, precision) -> mean
                 in float32, ``precision`` "f32" (``highest`` matmuls) or
                 "fp8" (the control).  It imports nothing of the program.
    "counts"     (optional, ``chipbench/flops.py`` when absent) the work the
                 algorithms need, from ``dims`` alone, for the readers that
                 count it: train_flops_per_token(dims, seq_len) for
                 ``train_mfu``; flash_call(dims, kind, rows, seq_len) for
                 ``flash_roofline.train``; decode_step(dims, contexts) and
                 prefill_chunk(dims, chunk, prior) for ``serve_mfu``;
                 paged_decode_call(dims, contexts, block) for
                 ``paged_decode_roofline.serve``.  The first returns
                 operations, the others (operations, bytes).  A driver puts
                 the module's path in its record under ``counts``, and the
                 readers load it from there (``counts_of``).
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import math
import shutil
import sys
from contextlib import contextmanager
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".chipbench_trace"


def key_of(seed: int):
    """A PRNG key from any non-negative seed, also those past 32 bits."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed % (2**31)), seed // (2**31))


class NoDevice(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.exists():
        raise FileNotFoundError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict  # configs/<config>.json
    traffic: dict  # traffic/<traffic>.json
    spec: dict  # workloads/<cell>.json
    end_to_end: list  # the manifest's metric entries that apply to this cell
    per_layer: list
    seed: int = 0
    seconds: float = 10.0
    trace: bool = False
    peak: dict | None = None
    devices: list | None = None

    @property
    def limits(self) -> dict:
        return self.spec.get("limits", {})


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, manifest_path: Path | None = None) -> Cell:
    manifest = load_json(manifest_path or ROOT / "BENCHMARK.json")
    entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    e2e = [m for m in manifest["end_to_end"] if _applies(m, name)]
    per_layer = [m for m in manifest["per_layer"] if _applies(m, name)]
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config=load_json(BENCH / "configs" / f"{entry['config']}.json"),
        traffic=load_json(BENCH / "traffic" / f"{entry['traffic']}.json"),
        spec=load_json(BENCH / "workloads" / f"{name}.json"),
        end_to_end=e2e,
        per_layer=per_layer,
    )


DEFAULT_COUNTS = "chipbench/flops.py"


@functools.lru_cache(maxsize=None)
def bench_module(rel: str):
    """The module at ``rel``, a path from the checkout's root that has to lie
    under ``chipbench/``; loaded once per process."""
    path = (ROOT / rel).resolve()
    if not path.is_relative_to(BENCH) or path.suffix != ".py":
        raise ValueError(f"{rel!r} is not a module under {BENCH.name}/")
    return _load_module(path, "chipbench_" + "_".join(path.relative_to(BENCH).with_suffix("").parts))


def load_reference(cell: Cell):
    """The plain reference the cell's configuration names."""
    return bench_module(cell.config["reference"])


def counts_name(config: dict) -> str:
    """Path of the counts module a configuration names (or the default)."""
    return config.get("counts", DEFAULT_COUNTS)


def counts_of(rec: dict):
    """The counts module a driver's record names."""
    return bench_module(rec.get("counts", DEFAULT_COUNTS))


def load_driver(kind: str):
    return _load_module(BENCH / "drivers" / f"{kind}.py", f"chipbench_driver_{kind}")


def load_metric(name: str):
    return _load_module(BENCH / "metrics" / f"{name}.py",
                        "chipbench_metric_" + name.replace(".", "_"))


def peaks() -> dict:
    return load_json(BENCH / "peaks.json")["kinds"]


def require_devices(chips: int):
    """The first ``chips`` accelerators and their peaks; raises ``NoDevice``
    when JAX finds no TPU, too few of them, or a kind without known peaks."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found {len(devices)}")
    kind = devices[0].device_kind
    table = peaks()
    if kind not in table:
        raise NoDevice(f"no peaks for device kind {kind!r} in peaks.json")
    return devices[:chips], table[kind]


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program cached, so only a checkout's first run compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class CompileCounter:
    """Counts XLA backend compilations (cache hits included, since a load
    from the cache inside the window stalls it too)."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.count += 1


@contextmanager
def profiled(cell: Cell):
    """Profile the enclosed block; yields a dict that holds the reduced
    trace on exit (the raw trace is deleted once reduced)."""
    import jax

    from chipbench.trace_reduce import reduce_dir

    out: dict = {}
    path = TRACE_DIR / cell.name
    shutil.rmtree(path, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # host spans only: no event per Python call
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("chipbench.window"):
            yield out
    finally:
        jax.profiler.stop_trace()
    out.update(reduce_dir(path, window_name="chipbench.window",
                          kernels=cell.spec.get("kernels", {}),
                          collectives=cell.spec.get("collectives")))
    shutil.rmtree(path, ignore_errors=True)


def memory_peak_bytes(devices) -> int | None:
    peaks_ = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks_.append(int(stats["peak_bytes_in_use"]))
    return max(peaks_) if peaks_ else None


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) of all values, linear between ranks."""
    xs = sorted(values)
    if not xs:
        return float("nan")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def result_line(cell: Cell, rec: dict) -> dict:
    """The last line: ``correct``, counts, the cell's metrics for this mode,
    the device, the breakdown of a traced run, and the compared numbers."""
    metrics = {}
    for m in (cell.per_layer if cell.trace else cell.end_to_end):
        value = load_metric(m["name"]).read(rec)
        if value is None:
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev = cell.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(cell.devices),
              "memory_peak_bytes": rec.get("memory_peak_bytes")}
    tr = rec.get("trace")
    if cell.trace and tr:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
    checks = rec["checks"]
    correct = bool(checks) and all(c["value"] <= c["limit"] for c in checks)
    correct = correct and rec["window_compiles"] == 0 and rec["failed"] == 0
    line = {"correct": correct, "attempted": rec["attempted"], "failed": rec["failed"],
            "metrics": metrics, "device": device}
    if cell.trace and tr:
        line["breakdown"] = {"device_ops": tr["device_ops"][:10],
                             "idle_gaps": tr["idle_gaps"][:10]}
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]} for c in checks}
    line["checks"]["window_compiles"] = {"value": rec["window_compiles"], "limit": 0}
    return line


def print_checks(line: dict, out=sys.stderr) -> None:
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=out, flush=True)
