"""Real prompt tokens of the requests admitted in the window that were served
from adopted pool blocks, over all their real prompt tokens, in percent
(positions of left padding count for neither)."""


def read(rec):
    p = rec.get("prefix")
    if not p or not p["real"]:
        return None
    return 100.0 * p["adopted"] / p["real"]
