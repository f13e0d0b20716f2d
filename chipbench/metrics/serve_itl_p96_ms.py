"""96th percentile of every gap between consecutive tokens of every request,
over the gaps that ended in the window: the stalls a streaming reader sees
when another request's prompt is prefilled in the same step.  In chat about
5% of the gaps are steps that carry a prefill chunk (51-60 ms) and under 3%
last 60 ms or more; the 96th percentile sits inside that mode, where the
95th flips between it and the decode-only steps and the 99th lies near the
edge of the longer steps."""

from chipbench.harness import percentile


def read(rec):
    return 1e3 * percentile(rec["itl_s"], 96) if rec.get("itl_s") else None
