"""95th percentile of every gap between consecutive tokens of every request,
over the gaps that ended in the window."""

from chipbench.harness import percentile


def read(rec):
    return 1e3 * percentile(rec["itl_s"], 95) if rec.get("itl_s") else None
