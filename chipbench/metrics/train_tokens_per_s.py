"""Tokens of every training step finished in the window, over the window
(the last step ends in ``block_until_ready``)."""


def read(rec):
    return rec["tokens"] / rec["window_s"] if "tokens" in rec and "steps" in rec else None
