"""Flash-attention kernels (forward, dq, dkv) in the traced steps: the least
time their work needs on the chip (the record's counts module, flash_call, at
the bf16 and HBM peaks) over their measured kernel time, in percent.  Every
call of a kernel does one layer of the rows one device holds of a microbatch
(the trace's calls and seconds are averaged over devices); the remat forward
is a call like any other."""

from chipbench import harness
from chipbench.flops import least_seconds


def read(rec):
    tr = rec.get("trace")
    if not tr:
        return None
    d, peak, counts = rec["dims"], rec["peak"], harness.counts_of(rec)
    need = spent = 0.0
    for kind in ("fwd", "dq", "dkv"):
        k = tr["kernels"].get(f"flash_{kind}")
        if not k or k["calls"] == 0:
            continue
        f, b = counts.flash_call(d, kind, rec["rows_per_device"], rec["seq_len"])
        need += k["calls"] * least_seconds(f, b, peak)[0]
        spent += k["seconds"]
    return 100.0 * need / spent if spent > 0 else None
