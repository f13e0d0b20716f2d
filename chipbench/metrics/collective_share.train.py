"""Share of the traced window in which a collective runs on a device, hidden
or not, averaged over the devices, in percent: beside
``exposed_collective_share.train`` it tells a collective that was hidden
from one that was removed.  Nothing where the trace holds no collective."""


def read(rec):
    tr = rec.get("trace")
    if not tr or "steps" not in rec or not tr.get("collective_s"):
        return None
    return 100.0 * tr["collective_s"] / tr["window_s"]
