"""Counts of chipbench/flops.py, checked by hand at the Yi-6B widths."""

import json
from pathlib import Path

import pytest

from chipbench import flops
from chipbench.reference import llama

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def dims(name):
    return llama.dims_of(json.loads((CONFIGS / f"{name}.json").read_text()))


def test_matmul_params_two_layers_and_head():
    d = dims("yi6b-train-2l")
    # per layer: q 4096*4096, k and v 4096*512, o 4096*4096, gate/up/out 3*4096*11008
    layer = 2 * 4096 * 4096 + 2 * 4096 * 512 + 3 * 4096 * 11008
    assert flops.layer_matmul_params(d) == layer == 173_015_040
    assert flops.matmul_params(d) == 2 * layer + 4096 * 64000 == 608_174_080


def test_kv_bytes_per_position_is_64_kib():
    assert flops.kv_bytes_per_position(dims("yi6b-serve")) == 32 * 2 * 4 * 128 * 2 == 64 * 1024


def test_train_flops_per_token_at_2048():
    d = dims("yi6b-train-2l")
    attn = 2 * 4 * 32 * 128 * (2048 * 2049 / 2) / 2048  # two layers, QK^T and PV
    assert flops.train_flops_per_token(d, 2048) == pytest.approx(6 * 608_174_080 + 3 * attn)
    assert flops.train_flops_per_token(d, 2048) == pytest.approx(3.7498e9, rel=1e-4)


@pytest.mark.parametrize("kind,n_mm", [("fwd", 2), ("dq", 3), ("dkv", 4)])
def test_flash_call_operations(kind, n_mm):
    d = dims("yi6b-train-2l")
    f, b = flops.flash_call(d, kind, 1, 2048)
    assert f == 2 * n_mm * 32 * 128 * (2048 * 2049 // 2)
    q, kv = 2048 * 32 * 128 * 2, 2048 * 4 * 128 * 2
    assert b > q + 2 * kv


def test_paged_decode_call_reads_whole_blocks():
    d = dims("yi6b-serve")
    f, b = flops.paged_decode_call(d, [128, 129], 128)
    assert f == 4 * 32 * 128 * 257
    # 1 + 2 blocks of 128 positions, keys and values of 4 heads of 128 in bf16, plus q and o
    assert b == 3 * 128 * 2 * 4 * 128 * 2 + 2 * 2 * 32 * 128 * 2


def test_decode_step_reads_all_weights_and_the_live_cache():
    d = dims("yi6b-serve")
    f, b = flops.decode_step(d, [1000] * 16)
    assert b == flops.weight_bytes(d) + 16 * 1000 * 64 * 1024
    assert flops.weight_bytes(d) == pytest.approx(12.12e9 - 2 * 64000 * 4096, rel=0.01)
    assert f == 2 * flops.matmul_params(d) * 16 + 32 * 4 * 32 * 128 * 16 * 1000


def test_prefill_chunk_counts_prior_and_causal_pairs():
    d = dims("yi6b-serve")
    f0, _ = flops.prefill_chunk(d, 512, 0)
    f1, b1 = flops.prefill_chunk(d, 512, 512)
    assert f1 - f0 == 32 * 4 * 32 * 128 * 512 * 512
    assert b1 == flops.weight_bytes(d) + 1024 * 64 * 1024


def test_least_seconds_names_its_bound():
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.least_seconds(197e12, 1.0, peak) == (1.0, "compute")
    assert flops.least_seconds(1.0, 819e9, peak) == (1.0, "memory")
