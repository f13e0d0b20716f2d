"""A stand-in reference for the tests: the Llama reference, noting each call
in ``stub_calls.txt`` in the working directory."""

from chipbench.reference import llama


def _note(name: str) -> None:
    with open("stub_calls.txt", "a") as f:
        f.write(name + "\n")


def dims_of(config):
    _note("dims_of")
    return llama.dims_of(config)


def make_weights(dims, key, dtype):
    _note("make_weights")
    return llama.make_weights(dims, key, dtype)


def logits(dims, weights, tokens, precision="f32"):
    _note("logits")
    return llama.logits(dims, weights, tokens, precision)


def loss(dims, weights, tokens, targets, precision="f32"):
    _note("loss")
    return llama.loss(dims, weights, tokens, targets, precision)
