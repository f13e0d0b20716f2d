"""The paged-decode kernel in the traced stretch: the least time its calls
need (each reads every live block of every lane whole; the record's counts
module, paged_decode_call) over their measured kernel time, in percent.  A
decode step calls it once a layer; the need of a call is averaged over the
traced decode steps."""

from chipbench import harness
from chipbench.flops import least_seconds


def read(rec):
    tr = rec.get("trace")
    k = tr and tr["kernels"].get("paged_decode")
    steps = [c for c in rec.get("traced_decode_contexts", []) if c]
    if not k or not k["calls"] or not steps:
        return None
    d, peak, counts = rec["dims"], rec["peak"], harness.counts_of(rec)
    per_call = sum(least_seconds(*counts.paged_decode_call(d, c, rec["block"]), peak)[0]
                   for c in steps) / len(steps)
    return 100.0 * k["calls"] * per_call / k["seconds"]
