"""The request generator every serving mix is read by.

A mix file (``traffic/<name>.json``) gives

    clients     closed-loop clients: each sends its next request the moment
                its last one completes
    requests    how many requests the list holds (more than a run uses)
    prompt      {"min", "max"}: prompt lengths, log-uniform
    documents   optional {"count", "length", "zipf_s"}: each prompt is one of
                ``count`` shared documents (drawn Zipf) followed by a unique
                question whose length ``prompt`` gives
    output      {"min", "max"}: tokens to generate, log-uniform
    ramp_s      seconds the loop runs before the window opens

The sizes and their order are the same for every seed: lengths are the
distribution's quantiles at (i + 1/2)/n and documents are dealt by Zipf
weight in proportion, each list shuffled once in a fixed order; the seed
draws the token ids of documents and questions.  So every seed does the
same work in the same order, and its tails are those of one schedule.
"""

from __future__ import annotations

import numpy as np


def _loguniform_quantiles(lo: int, hi: int, n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    return np.rint(np.exp(np.log(lo) + u * (np.log(hi) - np.log(lo)))).astype(int)


def _zipf_deal(count: int, s: float, n: int) -> np.ndarray:
    """``n`` document ids with counts in proportion to 1/rank^s."""
    w = 1.0 / np.arange(1, count + 1) ** s
    cum = np.cumsum(w / w.sum())
    u = (np.arange(n) + 0.5) / n
    return np.searchsorted(cum, u)


def requests(mix: dict, vocab: int, seed: int) -> list[tuple[np.ndarray, int]]:
    """[(prompt tokens, tokens to generate)] in the order clients send them."""
    order, rng = np.random.default_rng(0), np.random.default_rng(seed)
    n = mix["requests"]
    p_len = order.permutation(_loguniform_quantiles(mix["prompt"]["min"], mix["prompt"]["max"], n))
    o_len = order.permutation(_loguniform_quantiles(mix["output"]["min"], mix["output"]["max"], n))
    docs = mix.get("documents")
    if docs:
        which = order.permutation(_zipf_deal(docs["count"], docs["zipf_s"], n))
        texts = rng.integers(1, vocab, size=(docs["count"], docs["length"]), dtype=np.int32)
    out = []
    for i in range(n):
        tail = rng.integers(1, vocab, size=int(p_len[i]), dtype=np.int32)
        prompt = np.concatenate([texts[which[i]], tail]) if docs else tail
        out.append((prompt, int(o_len[i])))
    return out


def padded_lengths(mix: dict, granule: int) -> list[int]:
    """Every padded prompt length the mix can reach (prompts pad up to a
    power-of-two number of ``granule`` blocks)."""
    base = mix["documents"]["length"] if mix.get("documents") else 0
    lo, hi = base + mix["prompt"]["min"], base + mix["prompt"]["max"]
    return sorted({pad_len(x, granule) for x in range(lo, hi + 1)})


def pad_len(n: int, granule: int) -> int:
    p = granule
    while p < n:
        p *= 2
    return p
