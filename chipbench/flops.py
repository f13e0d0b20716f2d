"""Operations and bytes that the algorithms need, from shapes alone.

``d`` is a configuration's ``dims`` (num_layers, d_model, num_heads,
num_kv_heads, head_dim, d_ff, vocab_size).  A matmul of (m, k) by (k, n)
costs 2*m*k*n operations.  Causal attention over s positions needs
s*(s+1)/2 query-key pairs.  Counts are of the work the algorithm needs, not
of what an implementation happens to do (recompute, masked tiles, padding
of a bucket), so a measured time against them gives a share that cannot pass
100%.
"""

from __future__ import annotations

import math

BF16 = 2


def layer_matmul_params(d: dict) -> int:
    D, hd, H, KV, F = d["d_model"], d["head_dim"], d["num_heads"], d["num_kv_heads"], d["d_ff"]
    return D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F


def matmul_params(d: dict) -> int:
    """Matmul weights of all layers plus the output head; the embedding is
    a gather and is left out."""
    return d["num_layers"] * layer_matmul_params(d) + d["d_model"] * d["vocab_size"]


def causal_pairs(s: int) -> int:
    return s * (s + 1) // 2


def kv_bytes_per_position(d: dict, dtype_bytes: int = BF16) -> int:
    """Keys and values of one position over all layers (64 KiB for Yi-6B)."""
    return d["num_layers"] * 2 * d["num_kv_heads"] * d["head_dim"] * dtype_bytes


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


def train_flops_per_token(d: dict, seq_len: int) -> float:
    """Forward and backward of one token of a causal sequence: 6 operations
    per matmul weight, and three times the forward attention (QK^T and PV,
    each 2*H*hd per pair)."""
    attn_fwd = d["num_layers"] * 4 * d["num_heads"] * d["head_dim"] * causal_pairs(seq_len) / seq_len
    return 6 * matmul_params(d) + 3 * attn_fwd


def flash_call(d: dict, kind: str, batch: int, seq_len: int) -> tuple[float, float]:
    """(operations, bytes) of one call of a flash-attention kernel over one
    layer and ``batch`` rows of a causal sequence, in bf16.

    fwd  S = QK^T and O = PV: 2 matmuls over the causal pairs; reads q, k, v,
         writes o and the float32 log-sum-exp.
    dq   recomputes S, forms dP = dO V^T and dQ = dS K: 3 matmuls; reads q,
         k, v, dO, lse and the row sums, writes dq.
    dkv  recomputes S, forms dP, dV = P^T dO and dK = dS^T Q: 4 matmuls;
         reads q, k, v, dO, lse and the row sums, writes dk and dv.
    """
    H, KV, hd = d["num_heads"], d["num_kv_heads"], d["head_dim"]
    pairs = batch * causal_pairs(seq_len)
    n_mm = {"fwd": 2, "dq": 3, "dkv": 4}[kind]
    flops = 2 * n_mm * H * hd * pairs
    q = batch * seq_len * H * hd * BF16
    kv = batch * seq_len * KV * hd * BF16
    stat = batch * seq_len * H * 4
    if kind == "fwd":
        nbytes = q + 2 * kv + q + stat
    elif kind == "dq":
        nbytes = q + 2 * kv + q + 2 * stat + q
    else:
        nbytes = q + 2 * kv + q + 2 * stat + 2 * kv
    return float(flops), float(nbytes)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def weight_bytes(d: dict, dtype_bytes: int = BF16) -> int:
    """Weights one model step reads: all matmul weights and the norms (the
    embedding is a gather of a few rows)."""
    norms = (2 * d["num_layers"] + 1) * d["d_model"]
    return (matmul_params(d) + norms) * dtype_bytes


def decode_step(d: dict, contexts: list[int]) -> tuple[float, float]:
    """(operations, bytes) of one decode step of ``len(contexts)`` lanes, lane
    i attending over ``contexts[i]`` positions (its own new one included)."""
    ctx = sum(contexts)
    flops = 2 * matmul_params(d) * len(contexts) + d["num_layers"] * 4 * d["num_heads"] * d["head_dim"] * ctx
    return float(flops), float(weight_bytes(d) + kv_bytes_per_position(d) * ctx)


def prefill_chunk(d: dict, chunk: int, prior: int) -> tuple[float, float]:
    """(operations, bytes) of one prefill chunk of ``chunk`` positions after
    ``prior`` positions already in the cache."""
    pairs = chunk * prior + causal_pairs(chunk)
    flops = 2 * matmul_params(d) * chunk + d["num_layers"] * 4 * d["num_heads"] * d["head_dim"] * pairs
    kv = kv_bytes_per_position(d)
    return float(flops), float(weight_bytes(d) + kv * prior + kv * chunk)


def paged_decode_call(d: dict, contexts: list[int], block: int) -> tuple[float, float]:
    """(operations, bytes) of one paged-decode kernel call: one layer, every
    lane; the kernel must read each lane's live blocks whole."""
    H, KV, hd = d["num_heads"], d["num_kv_heads"], d["head_dim"]
    flops = 4 * H * hd * sum(contexts)
    live = sum(math.ceil(c / block) * block for c in contexts)
    nbytes = live * 2 * KV * hd * BF16 + 2 * len(contexts) * H * hd * BF16
    return float(flops), float(nbytes)


def least_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip needs for the work, and which bound sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
