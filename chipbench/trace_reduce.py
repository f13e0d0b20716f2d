"""Reduce a profiler trace (``.xplane.pb``) to the numbers the metrics read.

The window is the host span named ``window_name`` (``chipbench.window``,
opened by the harness around the traced stretch).  On each device plane
(``/device:TPU:<n>``) the events of the ``XLA Ops`` line are the operations
that ran on the chip; the ``Async XLA Ops`` line holds the spans of
asynchronous operations, of which only collectives are read.  From them,
clipped to the window:

  busy_s               union of the operation intervals, averaged over devices
  window_s             length of the window
  device_ops           [name, seconds] by total time, summed over devices and
                       divided by their count; the name is the HLO
                       instruction's less its numeric suffix, with its
                       opcode (``fusion (fusion)``, ``train_step (custom-call)``);
                       loops and calls, whose time is their body's, are
                       left out
  kernels              {key: {"seconds", "calls"}} for each kernel asked for,
                       averaged over devices.  A kernel is a regular
                       expression searched in the operation's HLO text: the
                       trace names a Pallas call after the jitted function
                       that holds it, not after its kernel, so the pattern
                       matches the call's output types
  collective_s         time in which a collective runs on either line,
                       averaged over devices.  A collective is an operation
                       whose HLO text matches the ``collectives`` pattern
                       (the workload file's; by default the opcodes of
                       COLLECTIVES)
  exposed_collective_s time in which a collective runs on the ``XLA Ops``
                       line and no other operation (loops and calls left
                       out) does, averaged over devices
  idle_gaps            [label, seconds] of the longest gaps (over 1 us)
                       between device operations (on any device), labelled
                       with the innermost host span open at the gap's middle
                       on the thread that opened the window
"""

from __future__ import annotations

import glob
import os
import re

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "collective-permute",
               "all-to-all", "allgather", "allreduce", "reducescatter")
_DEFAULT_COLLECTIVE = "|".join(COLLECTIVES)
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
SKEW_NS = 10e6  # device events this far before the window may be skewed into it
MIN_GAP_NS = 1e3  # shorter idle gaps are not listed
# ops whose time is that of the ops they contain (a scan's while loop)
CONTAINERS = ("(while)", "(conditional)", "(call)")


def find_xplane(path: str) -> str:
    hits = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return hits[-1]


def _union(intervals):
    """Sorted, merged copy of [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _length(merged) -> float:
    return float(sum(e - s for s, e in merged))


def _subtract(a, b) -> float:
    """Length of merged intervals ``a`` not covered by merged ``b``."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


_SUFFIX = re.compile(r"\.\d+$")
_OPCODE = re.compile(r" ([a-z][\w-]*)\(")


def _op_name(text: str) -> str:
    """``%fusion.12 = bf16[..] fusion(..)`` -> ``fusion (fusion)``: the
    instruction's name less its numeric suffix, and its opcode."""
    lhs, _, rhs = text.partition(" = ")
    name = _SUFFIX.sub("", lhs.lstrip("%"))
    op = _OPCODE.search(rhs)
    return f"{name} ({op.group(1)})" if op else name


def _window(planes, window_name: str):
    for name, lines in planes:
        if not name.startswith("/host"):
            continue
        for _, events in lines:
            for ev in events:
                if ev.name == window_name:
                    return float(ev.start_ns), float(ev.start_ns + ev.duration_ns), events
    raise ValueError(f"no host span {window_name!r} in the trace")


def reduce_planes(planes, *, window_name: str = "chipbench.window", kernels=None,
                  collectives: str | None = None, n_gaps: int = 10, n_ops: int = 10) -> dict:
    planes = [(p.name, [(line.name, list(line.events)) for line in p.lines]) for p in planes]
    kernels = {k: re.compile(v) for k, v in (kernels or {}).items()}
    is_collective = (re.compile(collectives).search if collectives else
                     lambda text: re.search(_DEFAULT_COLLECTIVE, _op_name(text).lower()))
    w0, w1, host_events = _window(planes, window_name)
    host = sorted(((float(e.start_ns), float(e.start_ns + e.duration_ns), e.name)
                   for e in host_events
                   if e.name != window_name and w0 <= e.start_ns <= w1),
                  key=lambda t: t[0])
    devices = [(dict(lines)[OPS_LINE], dict(lines).get(ASYNC_LINE, [])) for name, lines in planes
               if name.startswith(DEVICE_PREFIX) and OPS_LINE in dict(lines)]
    if not devices:
        raise ValueError("no device plane with an 'XLA Ops' line in the trace")
    # The device clock runs about a millisecond behind the host's in these
    # traces.  Nothing runs on the chip before the window's first host span
    # dispatches it, so the first operation after the window opens is moved
    # to that span's start, and every device event with it.
    first = min((float(e.start_ns) for events, _ in devices for e in events
                 if e.start_ns >= w0 - SKEW_NS), default=w0)
    shift = max(0.0, (host[0][0] if host else w0) - first)
    busy, coll_s, exposed, all_merged = 0.0, 0.0, 0.0, []
    op_s: dict[str, float] = {}
    k_s = {k: 0.0 for k in kernels}
    k_n = {k: 0 for k in kernels}
    def clipped(events):
        for ev in events:
            s = float(ev.start_ns) + shift
            e = s + float(ev.duration_ns)
            s, e = max(s, w0), min(e, w1)
            if e > s:
                yield ev.name, s, e

    for events, async_events in devices:
        ops, coll, other = [], [], []
        for name, s, e in clipped(events):
            key = _op_name(name)
            if not key.endswith(CONTAINERS):
                op_s[key] = op_s.get(key, 0.0) + (e - s)
            for k, pattern in kernels.items():
                if pattern.search(name):
                    k_s[k] += e - s
                    k_n[k] += 1
            if is_collective(name):
                coll.append((s, e))
            else:
                ops.append((s, e))
                if not key.endswith(CONTAINERS):
                    other.append((s, e))
        merged = _union(ops + coll)
        all_merged.extend(merged)
        busy += _length(merged)
        in_flight = [(s, e) for name, s, e in clipped(async_events) if is_collective(name)]
        coll_s += _length(_union(coll + in_flight))
        exposed += _subtract(_union(coll), _union(other))
    n = len(devices)
    # gaps in which no device ran anything
    gaps, cur = [], w0
    for s, e in _union(all_merged):
        if s - cur > MIN_GAP_NS:
            gaps.append((cur, s))
        cur = max(cur, e)
    if w1 - cur > MIN_GAP_NS:
        gaps.append((cur, w1))
    gaps.sort(key=lambda g: g[0] - g[1])

    def label(t: float) -> str:
        best = None
        for s, e, name in host:
            if s > t:
                break
            if e >= t and (best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        return best[2] if best else "outside any host span"

    ops_sorted = sorted(op_s.items(), key=lambda kv: -kv[1])
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy / n / 1e9,
        "devices": n,
        "device_ops": [[k, v / n / 1e9] for k, v in ops_sorted[:n_ops]],
        "kernels": {k: {"seconds": k_s[k] / n / 1e9, "calls": k_n[k] / n} for k in kernels},
        "collective_s": coll_s / n / 1e9,
        "exposed_collective_s": exposed / n / 1e9,
        "idle_gaps": [[label((s + e) / 2), (e - s) / 1e9] for s, e in gaps[:n_gaps]],
    }


def reduce_dir(path: str, **kw) -> dict:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(find_xplane(path)).planes, **kw)
