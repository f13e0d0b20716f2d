"""Architecture + run configuration dataclasses and the shape table.

Every assigned architecture is a ``ModelConfig`` (exact numbers from the
public sources quoted in the task table) plus a ``reduced()`` variant used by
CPU smoke tests. ``SHAPES`` is the assigned input-shape set shared by all
LM-family archs.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Literal

# The ONE attn_impl="auto" flip point: sequences at or below this run dense
# attention, longer ones run the tiled lane (flash, or the Pallas kernels
# when attn_impl="pallas").  models/attention.py::resolve_impl and
# choose_attention both read it — do not fork it inline again.
FLASH_THRESHOLD = 1024


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 => d_model // num_heads

    # layer pattern (repeating period; num_layers % len(pattern) == 0, or
    # num_layers < len(pattern): the first num_layers layers of one period)
    pattern: tuple[str, ...] = ("attn",)  # 'attn' | 'attn_local' | 'mamba' | 'conv'
    ffn_pattern: tuple[str, ...] = ("dense",)  # 'dense' | 'moe'

    # attention details
    qkv_bias: bool = False
    attn_softcap: float | None = None
    final_softcap: float | None = None
    window: int | None = None  # sliding window for 'attn_local'
    rope_theta: float = 10_000.0
    causal: bool = True  # False => encoder-only (no decode shapes)
    qk_norm: bool = False  # RMSNorm over each head of q and k before RoPE

    # moe
    num_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    moe_impl: str = "gspmd"  # 'gspmd' | 'ep' (shard_map explicit all-to-all)
    # 'softmax': capacity dispatch over every expert, Switch aux loss;
    # 'sigmoid': sigmoid scores, a non-trained expert bias that only selects,
    # dropless grouped matmuls over the experts this layer holds
    router: str = "softmax"
    d_ff_expert: int = 0  # expert FFN width; 0 => d_ff
    experts_held: int = 0  # experts [0, held) a 'sigmoid' layer holds; 0 => num_experts

    # gated short convolution ('conv' mixer)
    conv_kernel: int = 3

    # ssm
    ssm_state: int = 16
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_dt_rank: int = 0  # 0 = ceil(d_model / 16)
    ssm_chunk: int = 256

    # io
    input_mode: str = "tokens"  # 'tokens' | 'embeddings' (audio/vlm stub frontend)
    norm_type: str = "rms"  # 'rms' | 'layer'
    norm_eps: float = 1e-6
    tie_embeddings: bool = False  # the output head is the embedding's transpose
    ffn_act: str = "silu"  # activation inside (GLU-style) FFN
    ffn_glu: bool = True  # gated FFN (SwiGLU); False => plain 2-layer MLP

    # numerics / structure
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    scan_layers: bool = True
    remat: bool = True
    xent_chunk: int = 512
    attn_impl: str = "auto"  # 'auto' | 'dense' | 'flash' | 'pallas'
    flash_threshold: int = FLASH_THRESHOLD  # auto: dense iff s <= threshold
    flash_q_block: int = 512
    flash_kv_block: int = 1024
    moe_groups: int = 0  # 0 => data shard count at call time

    # metadata
    source: str = ""

    def __post_init__(self):
        # JSON configurations give lists; the config stays hashable
        for name in ("pattern", "ffn_pattern"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        if self.num_layers < len(self.pattern):
            if len(self.ffn_pattern) == len(self.pattern):
                object.__setattr__(self, "ffn_pattern", self.ffn_pattern[: self.num_layers])
            object.__setattr__(self, "pattern", self.pattern[: self.num_layers])
        if self.num_layers % len(self.pattern) != 0:
            raise ValueError(f"{self.name}: num_layers % pattern period != 0")
        if len(self.ffn_pattern) not in (1, len(self.pattern)):
            # allow ffn_pattern either scalar-like or same period
            if len(self.pattern) % len(self.ffn_pattern) != 0:
                raise ValueError(f"{self.name}: ffn_pattern period mismatch")
        if self.router not in ("softmax", "sigmoid"):
            raise ValueError(f"{self.name}: unknown router {self.router!r}")
        if self.experts_held and self.router != "sigmoid":
            raise ValueError(f"{self.name}: only the sigmoid router holds a share of the experts")
        if self.held_experts > self.num_experts:
            raise ValueError(f"{self.name}: {self.held_experts} held experts of "
                             f"{self.num_experts}")

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.num_heads if self.num_heads else 0)

    @property
    def expert_width(self) -> int:
        return self.d_ff_expert or self.d_ff

    @property
    def held_experts(self) -> int:
        return self.experts_held or self.num_experts

    @property
    def period(self) -> int:
        return len(self.pattern)

    @property
    def repeats(self) -> int:
        return self.num_layers // self.period

    @property
    def dt_rank(self) -> int:
        return self.ssm_dt_rank or max(1, math.ceil(self.d_model / 16))

    def ffn_kind(self, pos: int) -> str:
        return self.ffn_pattern[pos % len(self.ffn_pattern)]

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: Literal["train", "prefill", "decode"]
    seq_len: int
    global_batch: int


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32_768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524_288, 1),
}

# Archs whose attention is purely quadratic skip long_500k; encoder-only archs
# skip decode shapes entirely (see DESIGN.md §6).
FULL_ATTENTION_ARCHS = {
    "yi-6b", "qwen2-7b", "llama3-405b", "dbrx-132b", "kimi-k2-1t-a32b", "internvl2-1b",
}
ENCODER_ONLY_ARCHS = {"hubert-xlarge"}


def cell_supported(arch_name: str, shape_name: str, causal: bool) -> tuple[bool, str]:
    from repro.configs import get_config

    shape = SHAPES[shape_name]
    # short-convolution layers train only (models.transformer.refuse_serving)
    if "conv" in get_config(arch_name).pattern and shape.kind != "train":
        return False, "short-convolution state has no serving cache yet (ROADMAP Reach 3)"
    if arch_name in ENCODER_ONLY_ARCHS and shape.kind == "decode":
        return False, "encoder-only: no decode step"
    if shape.name == "long_500k" and arch_name in FULL_ATTENTION_ARCHS:
        return False, "pure full-attention arch: 500k KV cache needs sub-quadratic attention"
    return True, ""
