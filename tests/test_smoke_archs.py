"""Per-arch smoke tests: every assigned architecture instantiates a REDUCED
config of the same family and runs one forward + one train step on CPU,
asserting output shapes and no NaNs (full configs are exercised only by the
dry-run via ShapeDtypeStructs)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, get_config
from repro.models import transformer as tf
from repro.optim import sgd
from repro.train import init_state, make_train_step

B, S = 2, 32


def _batch(cfg):
    if cfg.input_mode == "tokens":
        toks = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
        return {"tokens": toks, "targets": toks}
    emb = jax.random.normal(jax.random.key(1), (B, S, cfg.d_model), jnp.float32)
    tgt = jax.random.randint(jax.random.key(2), (B, S), 0, cfg.vocab_size)
    return {"embeddings": emb, "targets": tgt}


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_forward_loss(arch):
    cfg = get_config(arch, reduced=True)
    params = tf.init_params(cfg, jax.random.key(0))
    loss, metrics = jax.jit(lambda p, b: tf.loss_fn(cfg, p, b))(params, _batch(cfg))
    assert np.isfinite(float(loss)), arch
    assert float(loss) > 0


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_train_step(arch):
    cfg = get_config(arch, reduced=True)
    params = tf.init_params(cfg, jax.random.key(0))
    opt = sgd(momentum=0.9)
    state = init_state(params, opt)
    step = make_train_step(cfg, opt, num_micro=2, diversity_on=True)
    state2, metrics = jax.jit(step)(state, _batch(cfg), jnp.float32(0.01))
    assert np.isfinite(float(metrics["loss"])), arch
    assert int(state2.step) == 1
    # params actually moved
    moved = any(
        not np.allclose(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(state2.params))
    )
    assert moved, arch
    # diversity accumulators advanced
    assert float(state2.div_state.sample_count) == B
    assert float(state2.div_state.sq_norm_sum) > 0


@pytest.mark.parametrize("arch", [a for a in ARCH_NAMES
                                  if a != "hubert-xlarge" and "conv" not in get_config(a).pattern])
def test_decode_step(arch):
    cfg = get_config(arch, reduced=True)
    params = tf.init_params(cfg, jax.random.key(0))
    cache = tf.init_cache(cfg, B, 16)
    if cfg.input_mode == "tokens":
        tok = jnp.ones((B, 1), jnp.int32)
    else:
        tok = jnp.ones((B, 1, cfg.d_model), jnp.float32)
    logits, cache2 = jax.jit(lambda p, c, t: tf.decode_step(cfg, p, c, t))(params, cache, tok)
    assert logits.shape == (B, 1, cfg.vocab_size), arch
    assert np.all(np.isfinite(np.asarray(logits))), arch
    assert int(cache2["len"]) == 1
