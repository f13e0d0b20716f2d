"""Host time of the adaptation boundary (read the diversity signals, observe,
resize), from the drained queue to the new batch size: the window's total
over its tick count, in milliseconds."""


def read(rec):
    ticks = rec.get("tick_s")
    return 1e3 * sum(ticks) / len(ticks) if ticks else None
