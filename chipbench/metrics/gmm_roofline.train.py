"""Grouped-matmul kernels (``gmm`` and ``tgmm``) of the expert layers in the
traced steps: the least time their work needs on the chip (the record's
counts module, gmm_call, at the bf16 and HBM peaks) over their measured
kernel time, in percent.  Every call does one projection of one layer for
the rows one device holds of a microbatch, counted at the expected routed
rows (routed_rows); a cell without the kernels reads nothing."""

from chipbench import harness
from chipbench.flops import least_seconds


def read(rec):
    tr = rec.get("trace")
    if not tr:
        return None
    d, peak, counts = rec["dims"], rec["peak"], harness.counts_of(rec)
    if not hasattr(counts, "gmm_call"):
        return None
    rows = counts.routed_rows(d, rec["rows_per_device"], rec["seq_len"])
    need = spent = 0.0
    for kind in ("gmm", "tgmm"):
        k = tr["kernels"].get(kind)
        if not k or k["calls"] == 0:
            continue
        need += k["calls"] * least_seconds(*counts.gmm_call(d, kind, rows), peak)[0]
        spent += k["seconds"]
    return 100.0 * need / spent if spent > 0 else None
