"""Counts of chipbench/flops_lfm2.py at the widths of
configs/lfm2-8b-a1b-train-6l.json, checked by hand, and the grouped-matmul
roofline reader on a hand-built record."""

import json
from pathlib import Path

import pytest

from chipbench import flops_lfm2 as counts
from chipbench import harness
from chipbench.reference import lfm2_moe

CONFIG = Path(__file__).resolve().parents[1] / "configs" / "lfm2-8b-a1b-train-6l.json"
D = lfm2_moe.dims_of(json.loads(CONFIG.read_text()))


def test_dims_are_the_cut():
    assert D["attention_layers"] == [2] and D["num_layers"] == 6
    assert (D["held_experts"], D["routed_experts"]) == (8, 32)
    assert D["head_dim"] == 64 and D["top_k"] == 4


def test_matmul_params_count_the_held_experts_at_their_share():
    mixers = 5 * (4 * 2048 * 2048 + 3 * 2048) + (2 * 2048 * 2048 + 2 * 2048 * 512)
    dense = 2 * 3 * 2048 * 7168
    # each token routes 4 of 32 experts, so each of the 8 held sees 1/8 of them
    experts = 4 * (2048 * 32 + 1.0 * 3 * 2048 * 1792)
    assert counts.matmul_params(D) == pytest.approx(mixers + dense + experts + 2048 * 65536)
    assert counts.matmul_params(D) == pytest.approx(361e6, rel=0.01)


def test_train_flops_per_token_at_8192():
    attn = 4 * 32 * 64 * (8192 * 8193 / 2) / 8192
    assert counts.train_flops_per_token(D, 8192) == pytest.approx(
        6 * counts.matmul_params(D) + 3 * attn)
    assert counts.train_flops_per_token(D, 8192) == pytest.approx(2.27e9, rel=0.01)


@pytest.mark.parametrize("kind", ["gmm", "tgmm"])
def test_gmm_call_at_the_expected_rows(kind):
    rows = counts.routed_rows(D, 2, 8192)
    assert rows == 2 * 8192 * 4 * 8 / 32 == 16384
    f, b = counts.gmm_call(D, kind, rows)
    assert f == 2 * 16384 * 2048 * 1792
    assert b == (16384 * (2048 + 1792) + 8 * 2048 * 1792) * 2


def test_gmm_call_refuses_an_unknown_kind():
    with pytest.raises(ValueError):
        counts.gmm_call(D, "dense", 1)


def test_gmm_roofline_reader():
    """Twelve calls at exactly their least time read 100%; a record without
    the kernels, or whose counts module has no gmm_call, reads nothing."""
    reader = harness.load_metric("gmm_roofline.train")
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    rows = counts.routed_rows(D, 2, 8192)
    least = harness.bench_module("chipbench/flops.py").least_seconds(
        *counts.gmm_call(D, "gmm", rows), peak)[0]
    rec = {"dims": D, "peak": peak, "rows_per_device": 2, "seq_len": 8192,
           "counts": "chipbench/flops_lfm2.py",
           "trace": {"kernels": {"gmm": {"calls": 9, "seconds": 9 * least},
                                 "tgmm": {"calls": 3, "seconds": 6 * least}}}}
    assert reader.read(rec) == pytest.approx(100.0 * 12 / 15)
    assert reader.read({**rec, "trace": {"kernels": {}}}) is None
    assert reader.read({**rec, "counts": "chipbench/flops.py"}) is None
