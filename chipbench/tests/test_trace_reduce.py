"""The reduction from a profiler trace to busy time, kernel time, exposed
collectives and labelled idle gaps: on hand-made planes, and on the small
trace recorded on a v5e chip (chipbench/tests/data)."""

import json
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from chipbench import trace_reduce
from chipbench.tests import fixture_trace

MS = 1_000_000
WORKLOADS = Path(__file__).resolve().parents[1] / "workloads"


def ev(name, start_ms, dur_ms, **stats):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS,
              stats=list(stats.items()))


def planes(device_events, host_events, n_devices=1):
    host = NS(name="/host:CPU", lines=[NS(name="python", events=host_events)])
    devs = [NS(name=f"/device:TPU:{i}", lines=[NS(name="XLA Modules", events=[]),
                                               NS(name="XLA Ops", events=device_events)])
            for i in range(n_devices)]
    return [host] + devs


def test_busy_idle_and_labels():
    host = [ev("chipbench.window", 0, 100), ev("step", 0, 50), ev("host_wait", 60, 40)]
    dev = [ev("fusion.1", 0, 20), ev("fusion.2", 10, 20), ev("_flash_fwd_kernel.3", 40, 10)]
    r = trace_reduce.reduce_planes(planes(dev, host), kernels={"flash": "_flash_fwd_kernel"})
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.04)  # [0, 30] and [40, 50]
    assert r["kernels"]["flash"] == {"seconds": pytest.approx(0.01), "calls": 1}
    assert r["device_ops"][0] == ["fusion", pytest.approx(0.04)]
    # gaps: [50, 100] under host_wait at its middle, [30, 40] under step
    assert r["idle_gaps"][0] == ["host_wait", pytest.approx(0.05)]
    assert r["idle_gaps"][1] == ["step", pytest.approx(0.01)]


def test_loops_are_not_counted_twice():
    host = [ev("chipbench.window", 0, 100), ev("step", 0, 100)]
    dev = [ev("%while.3 = (f32[]) while(f32[] %p)", 0, 60), ev("%fusion.2 = f32[] fusion(%x)", 10, 20),
           ev("%fusion.7 = f32[] fusion(%y)", 40, 10)]
    r = trace_reduce.reduce_planes(planes(dev, host))
    assert r["device_ops"] == [["fusion (fusion)", pytest.approx(0.030)]]
    assert r["busy_s"] == pytest.approx(0.060)


def test_events_are_clipped_to_the_window():
    host = [ev("chipbench.window", 30, 20), ev("step", 30, 20)]
    dev = [ev("fusion", 0, 35), ev("fusion", 45, 15)]
    r = trace_reduce.reduce_planes(planes(dev, host))
    assert r["busy_s"] == pytest.approx(0.010)


def test_device_clock_skew_is_taken_out():
    """The first operation after the window opens starts no earlier than the
    host span that dispatched it."""
    host = [ev("chipbench.window", 10, 30), ev("step", 10, 5)]
    dev = [ev("fusion", 9, 4), ev("fusion", 20, 4)]
    r = trace_reduce.reduce_planes(planes(dev, host))
    assert r["busy_s"] == pytest.approx(0.008)
    assert r["idle_gaps"][0] == ["outside any host span", pytest.approx(0.015)]


def test_exposed_collectives_average_over_devices():
    host = [ev("chipbench.window", 0, 100)]
    dev = [ev("all-gather.1", 0, 30), ev("fusion.1", 20, 30), ev("reduce-scatter.2", 60, 10)]
    r = trace_reduce.reduce_planes(planes(dev, host, n_devices=2))
    assert r["devices"] == 2
    assert r["exposed_collective_s"] == pytest.approx(0.030)  # [0, 20] and [60, 70]
    assert r["busy_s"] == pytest.approx(0.060)


def test_missing_window_or_device_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes(planes([ev("fusion", 0, 1)], [ev("other", 0, 1)]))
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes(planes([], [ev("chipbench.window", 0, 1)], n_devices=0))


def test_recorded_v5e_trace():
    """The trace names a Pallas call after its jitted function; the cell's
    pattern finds the flash forward by its output types."""
    spec = json.loads((WORKLOADS / "yi6b-train-divebatch.json").read_text())
    r = trace_reduce.reduce_dir(str(fixture_trace.PATH), window_name="chipbench.window",
                                kernels=spec["kernels"])
    assert r["kernels"]["flash_dq"]["calls"] == r["kernels"]["flash_dkv"]["calls"] == 0
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["kernels"]["flash_fwd"]["calls"] == 3
    assert 0 < r["kernels"]["flash_fwd"]["seconds"] < r["busy_s"]
    # three 10 ms host waits with nothing on the chip, then the dispatch gaps
    assert [g[0] for g in r["idle_gaps"][:3]] == ["chipbench.host_wait"] * 3
    assert all(g[1] > 0.009 for g in r["idle_gaps"][:3])
    assert r["idle_gaps"][3][0] == "chipbench.step"


def planes_with_async(device_events, async_events, host_events):
    host = NS(name="/host:CPU", lines=[NS(name="python", events=host_events)])
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=device_events),
                                         NS(name="Async XLA Ops", events=async_events)])
    return [host, dev]


FSDP4 = json.loads((WORKLOADS / "yi6b-train-fsdp4.json").read_text())["collectives"]


@pytest.mark.parametrize("device,async_ops,share,total", [
    # a collective wholly under a fusion is hidden
    ([ev("%all-gather.1 = bf16[8] all-gather(bf16[2] %p)", 10, 30),
      ev("%fusion.2 = bf16[8] fusion(bf16[8] %q), kind=kLoop", 0, 50)], [], 0.0, 30.0),
    # a bare collective reads its share of the window
    ([ev("%collective-permute-done.3 = bf16[8] collective-permute-done(bf16[8] %s)", 20, 30),
      ev("%fusion.2 = bf16[8] fusion(bf16[8] %q), kind=kLoop", 60, 10)], [], 30.0, 30.0),
    # a loop around it hides nothing; an operation that reads a collective's
    # output is no collective; an async span in flight counts as collective
    # time only
    ([ev("%while.1 = (f32[]) while(f32[] %p)", 0, 100),
      ev("%all-reduce.5 = f32[8] all-reduce(f32[8] %g), to_apply=%add", 10, 10),
      ev("%fusion.7 = f32[8] fusion(f32[8] %all-reduce.5), kind=kLoop", 20, 10)],
     [ev("%collective-permute-start.4 = (bf16[8]) collective-permute-start(bf16[8] %w)", 40, 20)],
     10.0, 30.0),
])
def test_exposed_collective_share(device, async_ops, share, total):
    from chipbench import harness

    host = [ev("chipbench.window", 0, 100), ev("step", 0, 100)]
    tr = trace_reduce.reduce_planes(planes_with_async(device, async_ops, host), collectives=FSDP4)
    assert tr["collective_s"] == pytest.approx(total / 1000)
    rec = {"steps": 1, "trace": tr}
    assert harness.load_metric("exposed_collective_share.train").read(rec) == pytest.approx(share)
    assert harness.load_metric("collective_share.train").read(rec) == pytest.approx(total)


def test_no_collective_reads_nothing():
    from chipbench import harness

    host = [ev("chipbench.window", 0, 100)]
    tr = trace_reduce.reduce_planes(planes([ev("fusion.1", 0, 50)], host), collectives=FSDP4)
    assert tr["collective_s"] == 0
    for name in ("exposed_collective_share.train", "collective_share.train"):
        assert harness.load_metric(name).read({"steps": 1, "trace": tr}) is None
