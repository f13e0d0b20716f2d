"""A configuration brings its own reference and counts: the drivers make
their weights and compare with the module the configuration names, and the
counting readers use the module the record names (chipbench/flops.py when
the configuration names none)."""

import json
import re
from pathlib import Path

import pytest

from chipbench import harness
from chipbench.tests import rehearse

BENCH = Path(__file__).resolve().parents[1]
STUB_REFERENCE = "chipbench/tests/data/stub_reference.py"
STUB_COUNTS = "chipbench/tests/data/stub_counts.py"


def name_in_config(dest: Path, config: str, **keys) -> None:
    rehearse.scratch_copy(dest)
    path = dest / "chipbench" / "configs" / f"{config}.json"
    c = json.loads(path.read_text())
    c.update(keys)
    path.write_text(json.dumps(c))


def stub_calls(dest: Path) -> set:
    path = dest / "stub_calls.txt"
    return set(path.read_text().split()) if path.exists() else set()


@pytest.mark.parametrize("cell,config,used", [
    ("yi6b-train-divebatch", "yi6b-train-2l", {"dims_of", "make_weights", "loss"}),
    ("yi6b-serve-chat", "yi6b-serve", {"dims_of", "make_weights", "logits"}),
])
def test_a_configuration_brings_its_own_reference(tmp_path, cell, config, used):
    name_in_config(tmp_path, config, reference=STUB_REFERENCE)
    rc, last, err = rehearse.rehearse(cell, seconds=1, dest=tmp_path)
    assert rc == 0, err[-3000:]
    assert last["correct"] is True, last["checks"]
    assert used <= stub_calls(tmp_path)


def test_drivers_import_no_reference_by_name():
    for driver in (BENCH / "drivers").glob("*.py"):
        assert not re.search(r"reference\.\w+|reference import", driver.read_text()), driver


@pytest.mark.parametrize("cell,config,used", [
    ("yi6b-train-divebatch", "yi6b-train-2l", {"train_flops_per_token", "flash_call"}),
    ("yi6b-serve-rag", "yi6b-serve", {"decode_step", "prefill_chunk"}),
])
def test_the_readers_count_with_the_module_the_record_names(tmp_path, cell, config, used):
    name_in_config(tmp_path, config, counts=STUB_COUNTS)
    rc, last, err = rehearse.rehearse(cell, seconds=2, trace=1, dest=tmp_path)
    assert rc == 0, err[-3000:]
    assert last["metrics"], last
    assert used <= stub_calls(tmp_path)


def test_counts_default_to_flops_and_follow_the_record(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    d = {"num_layers": 2, "d_model": 4096, "num_heads": 32, "num_kv_heads": 4,
         "head_dim": 128, "d_ff": 11008, "vocab_size": 64000}
    trace = {"kernels": {"flash_fwd": {"seconds": 0.5, "calls": 8}}}
    rec = {"steps": 4, "tokens": 2048 * 16, "window_s": 2.0, "dims": d, "seq_len": 2048,
           "chips": 1, "rows_per_device": 1, "trace": trace,
           "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    assert harness.counts_name({}) == harness.DEFAULT_COUNTS
    for metric in ("train_mfu", "flash_roofline.train"):
        reader = harness.load_metric(metric)
        default = reader.read(rec)
        assert default == reader.read(dict(rec, counts=harness.DEFAULT_COUNTS))
        assert reader.read(dict(rec, counts=STUB_COUNTS)) == pytest.approx(2 * default)
    assert {"train_flops_per_token", "flash_call"} <= stub_calls(tmp_path)


def test_a_module_outside_the_benchmark_is_refused():
    with pytest.raises(ValueError):
        harness.bench_module("src/repro/__init__.py")
    with pytest.raises(ValueError):
        harness.bench_module("chipbench/../src/repro/__init__.py")
