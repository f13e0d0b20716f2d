"""95th percentile, over every request whose first token came in the window,
of the time from the client's send to the end of the step that produced it."""

from chipbench.harness import percentile


def read(rec):
    return 1e3 * percentile(rec["ttft_s"], 95) if rec.get("ttft_s") else None
