"""lfm2-8b-a1b [hybrid moe] — gated short convolutions (kernel 3) with six
full-attention layers (GQA 32Q/8KV of head_dim 64, per-head q/k RMSNorm).
24L d_model=2048, 2 leading dense SwiGLU layers of width 7168, then 22 MoE
layers of 32 experts of width 1792, top-4, sigmoid router whose expert
bias only selects. Vocab 65536, tied head, norm eps 1e-5.
[hf:LiquidAI/LFM2-8B-A1B config.json]. The 24 layers are not periodic, so
the pattern is one period of 24; a cut to fewer layers keeps the first ones
(ModelConfig.__post_init__)."""

from repro.configs.base import ModelConfig

_ATTENTION = (2, 6, 10, 14, 18, 21)
_LAYERS = 24
_DENSE = 2

CONFIG = ModelConfig(
    name="lfm2-8b-a1b",
    family="hybrid",
    num_layers=_LAYERS,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    d_ff=7168,
    d_ff_expert=1792,
    vocab_size=65_536,
    pattern=tuple("attn" if i in _ATTENTION else "conv" for i in range(_LAYERS)),
    ffn_pattern=tuple("dense" if i < _DENSE else "moe" for i in range(_LAYERS)),
    num_experts=32,
    top_k=4,
    router="sigmoid",
    conv_kernel=3,
    qk_norm=True,
    rope_theta=1_000_000.0,
    norm_eps=1e-5,
    tie_embeddings=True,
    source="hf:LiquidAI/LFM2-8B-A1B",
)


def reduced() -> ModelConfig:
    """Six layers (conv, conv, attn, conv, conv, conv; two dense FFNs, four
    MoE) at small widths, holding experts 0-3 of 8."""
    return CONFIG.replace(
        num_layers=6, d_model=64, num_heads=4, num_kv_heads=2,
        d_ff=96, d_ff_expert=32, vocab_size=251, num_experts=8, top_k=2,
        experts_held=4, param_dtype="float32", compute_dtype="float32",
        xent_chunk=64, remat=False,
    )
