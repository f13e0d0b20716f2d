"""The synthetic LM corpus: an order-1 Markov chain over a Zipfian successor
table, learnable and made from a seed.  The chain is the one the repository's
``data/synthetic.TokenStream`` defines (a successor table of ``branch``
entries per state, the successor picked with Zipf weights of exponent 1.1);
this copy draws all rows of a batch at once, one position at a time, so a
pool of sequences is built in set-up in well under a second.
"""

from __future__ import annotations

import numpy as np


def rows(vocab: int, n_rows: int, length: int, seed: int, *,
         branch: int = 64, zipf_s: float = 1.1, states: int = 4096) -> np.ndarray:
    """(n_rows, length) int32 tokens; every row its own chain from ``seed``."""
    rng = np.random.default_rng(seed)
    n_states = min(vocab, states)
    succ = rng.integers(0, vocab, size=(n_states, branch), dtype=np.int64)
    w = 1.0 / np.arange(1, branch + 1) ** zipf_s
    choices = rng.choice(branch, size=(length, n_rows), p=w / w.sum())
    state = rng.integers(0, n_states, size=n_rows)
    out = np.empty((n_rows, length), np.int32)
    for i in range(length):
        nxt = succ[state, choices[i]]
        out[:, i] = nxt % vocab
        state = nxt % n_states
    return out


def lm_pool(vocab: int, n_rows: int, seq_len: int, seed: int, **kw) -> dict:
    """``n_rows`` distinct training rows: tokens and next-token targets."""
    toks = rows(vocab, n_rows, seq_len + 1, seed, **kw)
    return {"tokens": toks[:, :-1].copy(), "targets": toks[:, 1:].copy()}
