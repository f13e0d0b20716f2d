"""Locks every assigned architecture to the assignment table's exact numbers
and validates derived parameter counts against the public model sizes."""

import numpy as np
import pytest

from repro.configs import ARCH_NAMES, SHAPES, cell_supported, get_config, input_specs
from repro.models import transformer as tf
from repro.utils import pytree as ptu

# (layers, d_model, heads, kv, d_ff, vocab) straight from the assignment
ASSIGNED = {
    "internvl2-1b": (24, 896, 14, 2, 4864, 151_655),
    "falcon-mamba-7b": (64, 4096, 0, 0, 0, 65_024),
    "kimi-k2-1t-a32b": (61, 7168, 64, 8, 2048, 163_840),
    "dbrx-132b": (40, 6144, 48, 8, 10_752, 100_352),
    "yi-6b": (32, 4096, 32, 4, 11_008, 64_000),
    "gemma2-27b": (46, 4608, 32, 16, 36_864, 256_000),
    "qwen2-7b": (28, 3584, 28, 4, 18_944, 152_064),
    "llama3-405b": (126, 16_384, 128, 8, 53_248, 128_256),
    "hubert-xlarge": (48, 1280, 16, 16, 5120, 504),
    "jamba-v0.1-52b": (32, 4096, 32, 8, 14_336, 65_536),
    "lfm2-8b-a1b": (24, 2048, 32, 8, 7168, 65_536),
}

# public total parameter counts (billions) with tolerance
PARAM_SANITY = {
    "falcon-mamba-7b": (7.3, 0.5),
    "kimi-k2-1t-a32b": (1041, 40),
    "dbrx-132b": (132, 5),
    "yi-6b": (6.1, 0.3),
    "gemma2-27b": (27, 3),
    "qwen2-7b": (7.6, 0.5),
    "llama3-405b": (405, 10),
    "jamba-v0.1-52b": (52, 3),
    "hubert-xlarge": (1.0, 0.2),
    "lfm2-8b-a1b": (8.3, 0.1),
}


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_assignment_numbers(arch):
    cfg = get_config(arch)
    L, d, h, kv, ff, v = ASSIGNED[arch]
    assert cfg.num_layers == L
    assert cfg.d_model == d
    assert cfg.num_heads == h
    assert cfg.num_kv_heads == kv
    assert cfg.d_ff == ff
    assert cfg.vocab_size == v


@pytest.mark.parametrize("arch", sorted(PARAM_SANITY))
def test_param_counts_match_public_sizes(arch):
    cfg = get_config(arch)
    specs = tf.param_specs(cfg)
    n = ptu.tree_count(specs) / 1e9
    want, tol = PARAM_SANITY[arch]
    assert abs(n - want) <= tol, f"{arch}: {n:.2f}B vs {want}B"


def test_arch_features():
    assert get_config("qwen2-7b").qkv_bias
    g = get_config("gemma2-27b")
    assert g.attn_softcap == 50.0 and g.final_softcap == 30.0
    assert g.pattern == ("attn_local", "attn") and g.window == 4096
    k = get_config("kimi-k2-1t-a32b")
    assert k.num_experts == 384 and k.top_k == 8
    j = get_config("jamba-v0.1-52b")
    assert j.pattern.count("attn") == 1 and len(j.pattern) == 8  # 1:7
    assert j.ffn_pattern.count("moe") == 4  # every other layer
    assert not get_config("hubert-xlarge").causal
    assert get_config("internvl2-1b").input_mode == "embeddings"
    assert get_config("falcon-mamba-7b").pattern == ("mamba",)


def test_cell_matrix_counts():
    run = skip = 0
    for a in ARCH_NAMES:
        cfg = get_config(a)
        for s in SHAPES:
            ok, why = cell_supported(a, s, cfg.causal)
            run += ok
            skip += not ok
            if not ok:
                assert why  # every skip carries a reason
    # 40 assigned cells, and lfm2-8b-a1b's 4 of which it trains one
    assert run == 33 and skip == 11


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_input_specs_all_supported_shapes(arch):
    cfg = get_config(arch)
    for sname, shape in SHAPES.items():
        ok, _ = cell_supported(arch, sname, cfg.causal)
        if not ok:
            continue
        specs = input_specs(cfg, shape)
        assert specs  # builds ShapeDtypeStructs without allocation
        if shape.kind == "decode":
            assert "cache" in specs and "tokens" in specs
        else:
            assert "batch" in specs
