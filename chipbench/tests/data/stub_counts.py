"""A stand-in counts module for the tests: chipbench/flops.py's counts, each
doubled, noting each call in ``stub_calls.txt`` in the working directory."""

from chipbench import flops


def _note(name: str) -> None:
    with open("stub_calls.txt", "a") as f:
        f.write(name + "\n")


def train_flops_per_token(d, seq_len):
    _note("train_flops_per_token")
    return 2 * flops.train_flops_per_token(d, seq_len)


def flash_call(d, kind, rows, seq_len):
    _note("flash_call")
    return tuple(2 * x for x in flops.flash_call(d, kind, rows, seq_len))


def decode_step(d, contexts):
    _note("decode_step")
    return tuple(2 * x for x in flops.decode_step(d, contexts))


def prefill_chunk(d, chunk, prior):
    _note("prefill_chunk")
    return tuple(2 * x for x in flops.prefill_chunk(d, chunk, prior))


def paged_decode_call(d, contexts, block):
    _note("paged_decode_call")
    return tuple(2 * x for x in flops.paged_decode_call(d, contexts, block))
