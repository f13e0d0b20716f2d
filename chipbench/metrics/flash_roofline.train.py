"""Flash-attention kernels (forward, dq, dkv) in the traced steps: the least
time their work needs on the chip (flops.flash_call, at the bf16 and HBM
peaks) over their measured kernel time, in percent.  Every call of a kernel
does one layer of one microbatch; the remat forward is a call like any
other."""

from chipbench import flops


def read(rec):
    tr = rec.get("trace")
    if not tr:
        return None
    d, peak = rec["dims"], rec["peak"]
    need = spent = 0.0
    for kind in ("fwd", "dq", "dkv"):
        k = tr["kernels"].get(f"flash_{kind}")
        if not k or k["calls"] == 0:
            continue
        f, b = flops.flash_call(d, kind, rec["micro_batch"], rec["seq_len"])
        need += k["calls"] * flops.least_seconds(f, b, peak)[0]
        spent += k["seconds"]
    return 100.0 * need / spent if spent > 0 else None
