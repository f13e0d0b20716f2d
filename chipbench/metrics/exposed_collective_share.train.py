"""Share of the traced window in which a collective (the workload file's
``collectives`` pattern: all-gather, reduce-scatter, all-reduce and their
async halves) runs on a device and no other operation runs on it, averaged
over the devices, in percent.  Nothing where the trace holds no collective."""


def read(rec):
    tr = rec.get("trace")
    if not tr or "steps" not in rec or not tr.get("collective_s"):
        return None
    return 100.0 * tr["exposed_collective_s"] / tr["window_s"]
