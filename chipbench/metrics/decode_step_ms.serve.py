"""Host time of an engine step that ran no prefill chunk (retire, policy,
admit, one decode step, the token read): the median over the window."""

import statistics


def read(rec):
    xs = rec.get("decode_only_step_s")
    return 1e3 * statistics.median(xs) if xs else None
