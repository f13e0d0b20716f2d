"""The whole serving step's share of the chip's peak: over the window's
steps, the sum of the least time each program needs (a decode step reads all
weights and the live KV; a prefill chunk computes its positions; the
record's counts module), each the larger of operations over the bf16 peak
and bytes over HBM bandwidth, over the window, in percent."""

from chipbench import harness
from chipbench.flops import least_seconds


def read(rec):
    work = rec.get("step_work")
    if not work:
        return None
    d, peak, counts = rec["dims"], rec["peak"], harness.counts_of(rec)
    need = 0.0
    for s in work:
        if s["contexts"]:
            need += least_seconds(*counts.decode_step(d, s["contexts"]), peak)[0]
        for chunk, prior in s["chunks"]:
            need += least_seconds(*counts.prefill_chunk(d, chunk, prior), peak)[0]
    return 100.0 * need / rec["window_s"]
