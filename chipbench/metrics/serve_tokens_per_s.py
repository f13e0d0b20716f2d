"""Output tokens delivered to requests in the window, over the window."""


def read(rec):
    return rec["serve_tokens"] / rec["window_s"] if "serve_tokens" in rec else None
