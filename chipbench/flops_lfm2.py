"""Operations and bytes that LFM2-MoE's algorithms need, from shapes alone
(the counts module of ``configs/lfm2-8b-a1b-train-6l.json``; the
conventions of ``chipbench/flops.py``).

``d`` is the reference's ``dims``.  The held experts count at their expected
share: each token routes to ``top_k`` of ``routed_experts``, so each of the
``held_experts`` here sees ``top_k / routed_experts`` of the tokens.
"""

from __future__ import annotations

from chipbench.flops import BF16, causal_pairs, flash_call  # noqa: F401 (the attention layers)


def expected_share(d: dict) -> float:
    """Held expert-assignments per token: top_k x held / routed."""
    return d["top_k"] * d["held_experts"] / d["routed_experts"]


def routed_rows(d: dict, sequences: int, seq_len: int) -> float:
    """Expected assignments to the held experts in ``sequences`` rows."""
    return sequences * seq_len * expected_share(d)


def matmul_params(d: dict) -> float:
    """Matmul weights one token uses (the held experts at their expected
    share per expert, the convolution taps, the routers and the tied head;
    the embedding gather left out)."""
    D, hd, H, KV = d["d_model"], d["head_dim"], d["num_heads"], d["num_kv_heads"]
    L, attn = d["num_layers"], len(d["attention_layers"])
    dense, moe = d["num_dense_layers"], d["num_layers"] - d["num_dense_layers"]
    mixers = attn * (2 * D * H * hd + 2 * D * KV * hd) + (L - attn) * (4 * D * D + d["conv_kernel"] * D)
    ffn = dense * 3 * D * d["d_ff"]
    experts = moe * (D * d["routed_experts"]
                     + expected_share(d) * 3 * D * d["d_expert"])
    return mixers + ffn + experts + D * d["vocab_size"]


def train_flops_per_token(d: dict, seq_len: int) -> float:
    """Forward and backward of one token: 6 operations per matmul weight it
    uses, and three times the forward causal attention of each attention
    layer."""
    attn_fwd = len(d["attention_layers"]) * 4 * d["num_heads"] * d["head_dim"] \
        * causal_pairs(seq_len) / seq_len
    return 6 * matmul_params(d) + 3 * attn_fwd


def gmm_call(d: dict, kind: str, rows: float) -> tuple[float, float]:
    """(operations, bytes) of one grouped-matmul call of an expert layer over
    ``rows`` routed rows, in bf16.  Every call multiplies rows of one width
    of (d_model, d_expert) by the held experts' matrices of the two:

    gmm   rows x k by (held, k, n) -> rows x n, {k, n} = {d_model, d_expert}:
          the gate and up projections, the down projection, and the three
          input gradients (the right operand transposed)
    tgmm  (k x rows) by (rows x n) per expert -> (held, k, n): the three
          weight gradients

    Both read the rows of both widths and the held experts' matrix once and
    write the other, so they need the same."""
    if kind not in ("gmm", "tgmm"):
        raise ValueError(f"unknown grouped-matmul kind {kind!r}")
    D, F, E = d["d_model"], d["d_expert"], d["held_experts"]
    return float(2 * rows * D * F), float((rows * (D + F) + E * D * F) * BF16)
