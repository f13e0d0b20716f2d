"""Share of the traced stretch in which no operation ran on the chip, in
percent (averaged over the chips used)."""


def read(rec):
    tr = rec.get("trace")
    if not tr or "serve_tokens" not in rec:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
