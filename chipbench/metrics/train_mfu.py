"""Training tokens/s times the operations one token needs (forward and
backward: 6 per matmul weight with the head, the embedding gather left out,
plus causal attention; recompute not counted; the record's counts module),
over chips times the bf16 peak, in percent."""

from chipbench import harness


def read(rec):
    if "steps" not in rec:
        return None
    rate = rec["tokens"] / rec["window_s"]
    per_token = harness.counts_of(rec).train_flops_per_token(rec["dims"], rec["seq_len"])
    return 100.0 * rate * per_token / (rec["chips"] * rec["peak"]["bf16_flops_per_s"])
