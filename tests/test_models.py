"""Model-zoo parity and invariant tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models import transformer as tf
from repro.models.attention import attention, flash_attention

KEY = jax.random.key(0)


def _tiny(**kw) -> ModelConfig:
    base = dict(
        name="tiny", family="dense", num_layers=4, d_model=64, num_heads=4,
        num_kv_heads=2, d_ff=128, vocab_size=97, param_dtype="float32",
        compute_dtype="float32", xent_chunk=16, remat=False,
    )
    base.update(kw)
    return ModelConfig(**base)


class TestFlashAttention:
    @pytest.mark.parametrize("causal,window,softcap", [
        (True, None, None), (True, 8, None), (True, None, 30.0), (False, None, None),
    ])
    def test_fwd_bwd_vs_dense(self, causal, window, softcap):
        q = jax.random.normal(jax.random.key(1), (2, 64, 4, 16))
        k = jax.random.normal(jax.random.key(2), (2, 64, 2, 16))
        v = jax.random.normal(jax.random.key(3), (2, 64, 2, 16))
        o_d = attention(q, k, v, causal=causal, window=window, softcap=softcap)
        o_f = flash_attention(q, k, v, causal, window, softcap, 16, 32)
        np.testing.assert_allclose(np.asarray(o_d), np.asarray(o_f), atol=2e-5)
        gd = jax.grad(lambda *a: attention(*a, causal=causal, window=window,
                                           softcap=softcap).sum(), argnums=(0, 1, 2))(q, k, v)
        gf = jax.grad(lambda *a: flash_attention(*a, causal, window, softcap,
                                                 16, 32).sum(), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gd, gf):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


class TestChunkedXent:
    # (batch, seq, chunk, softcap): the backward forms dW from groups of
    # ceil(XENT_DW_ROWS / (batch x chunk)) chunks, at most every chunk
    @pytest.mark.parametrize("b,s,chunk,softcap", [
        (2, 32, 8, None),                          # one group
        (2, 4096, 16, None),                       # 4 groups of 64 chunks
        (1, 16 * (2 * 128 + 3), 16, None),         # 2 groups of 128 and 3 chunks
        (2, 4096, 16, 30.0),                       # several groups, softcapped
    ], ids=["one_group", "groups", "uneven_groups", "softcap"])
    def test_matches_naive(self, b, s, chunk, softcap):
        x = jax.random.normal(jax.random.key(4), (b, s, 16))
        kern = jax.random.normal(jax.random.key(5), (16, 51)) * 0.1
        tgt = jax.random.randint(jax.random.key(6), (b, s), 0, 51)

        def naive(x, k):
            logits = (x @ k).astype(jnp.float32)
            if softcap is not None:
                logits = jnp.tanh(logits / softcap) * softcap
            lse = jax.scipy.special.logsumexp(logits, -1)
            t = jnp.take_along_axis(logits, tgt[..., None], -1)[..., 0]
            return jnp.mean(lse - t)

        def chunked(x, k):
            return tf.xent_chunked(x, k, tgt, chunk, softcap)

        np.testing.assert_allclose(float(naive(x, kern)), float(chunked(x, kern)),
                                   rtol=1e-6)
        g1 = jax.grad(naive, argnums=(0, 1))(x, kern)
        g2 = jax.grad(chunked, argnums=(0, 1))(x, kern)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


class TestMoE:
    def test_apply_matches_reference(self):
        p = moe_lib.moe_init(KEY, 32, 64, 8)
        x = jax.random.normal(jax.random.key(7), (2, 16, 32))
        y1, _ = moe_lib.moe_apply(p, x, top_k=2, capacity_factor=8.0, groups=2)
        y2 = moe_lib.moe_reference(p, x, top_k=2)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)

    def test_group_invariance(self):
        p = moe_lib.moe_init(KEY, 16, 32, 4)
        x = jax.random.normal(jax.random.key(8), (4, 8, 16))
        y1, _ = moe_lib.moe_apply(p, x, top_k=2, capacity_factor=8.0, groups=1)
        y2, _ = moe_lib.moe_apply(p, x, top_k=2, capacity_factor=8.0, groups=4)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)

    def test_capacity_drops_are_bounded(self):
        """With tight capacity some tokens drop — output stays finite and
        close in norm."""
        p = moe_lib.moe_init(KEY, 16, 32, 4)
        x = jax.random.normal(jax.random.key(9), (2, 32, 16))
        y, aux = moe_lib.moe_apply(p, x, top_k=2, capacity_factor=1.0, groups=1)
        assert np.all(np.isfinite(np.asarray(y)))
        assert float(aux) > 0

    def test_grads_flow(self):
        p = moe_lib.moe_init(KEY, 16, 32, 4)
        x = jax.random.normal(jax.random.key(10), (2, 8, 16))
        g = jax.grad(lambda pp: moe_lib.moe_apply(pp, x, top_k=2, capacity_factor=4.0)[0].sum())(p)
        for leaf in jax.tree.leaves(g):
            assert np.all(np.isfinite(np.asarray(leaf)))


class TestMamba:
    def test_chunked_scan_invariant(self):
        p = ssm_lib.mamba_init(KEY, 32, d_state=8)
        x = jax.random.normal(jax.random.key(11), (2, 32, 32))
        y1 = ssm_lib.mamba_apply(p, x, d_state=8, chunk=4)
        y2 = ssm_lib.mamba_apply(p, x, d_state=8, chunk=32)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)

    def test_decode_matches_prefill(self):
        p = ssm_lib.mamba_init(KEY, 16, d_state=4, conv_dim=4)
        x = jax.random.normal(jax.random.key(12), (2, 12, 16))
        y_full = ssm_lib.mamba_apply(p, x, d_state=4, chunk=4)
        state = ssm_lib.mamba_decode_init(2, 16, 4, 2, 4)
        outs = []
        for t in range(12):
            o, state = ssm_lib.mamba_decode_step(p, state, x[:, t : t + 1], d_state=4)
            outs.append(o)
        y_step = jnp.concatenate(outs, axis=1)
        np.testing.assert_allclose(np.asarray(y_full), np.asarray(y_step), atol=1e-4)


class TestTransformer:
    def test_scan_eager_parity(self):
        cfg = _tiny(scan_layers=True)
        params = tf.init_params(cfg, KEY)
        batch = {"tokens": jnp.ones((2, 16), jnp.int32),
                 "targets": jnp.ones((2, 16), jnp.int32)}
        l1, _ = tf.loss_fn(cfg, params, batch)
        l2, _ = tf.loss_fn(cfg.replace(scan_layers=False), params, batch)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)

    def test_remat_parity(self):
        cfg = _tiny(remat=True)
        params = tf.init_params(cfg, KEY)
        batch = {"tokens": jnp.ones((2, 16), jnp.int32),
                 "targets": jnp.ones((2, 16), jnp.int32)}
        l1, _ = tf.loss_fn(cfg, params, batch)
        l2, _ = tf.loss_fn(cfg.replace(remat=False), params, batch)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)

    @pytest.mark.parametrize("cfg_kw", [
        {},  # dense GQA
        {"pattern": ("attn_local", "attn"), "window": 8,
         "attn_softcap": 50.0, "final_softcap": 30.0},  # gemma2-style
        {"qkv_bias": True},  # qwen2-style
    ])
    def test_decode_matches_prefill(self, cfg_kw):
        cfg = _tiny(**cfg_kw)
        params = tf.init_params(cfg, KEY)
        toks = jax.random.randint(jax.random.key(13), (2, 16), 0, 97)
        logits_pre, _ = tf.prefill_step(cfg, params, {"tokens": toks})
        cache = tf.init_cache(cfg, 2, 16)
        for t in range(16):
            logits_dec, cache = tf.decode_step(cfg, params, cache, toks[:, t : t + 1])
        np.testing.assert_allclose(
            np.asarray(logits_dec), np.asarray(logits_pre), atol=3e-3
        )

    def test_encoder_mode(self):
        cfg = _tiny(causal=False, input_mode="embeddings", norm_type="layer",
                    ffn_glu=False, ffn_act="gelu")
        params = tf.init_params(cfg, KEY)
        batch = {"embeddings": jax.random.normal(KEY, (2, 16, 64)),
                 "targets": jnp.ones((2, 16), jnp.int32)}
        loss, _ = tf.loss_fn(cfg, params, batch)
        assert np.isfinite(float(loss))
        logits, cache = tf.prefill_step(cfg, params, batch)
        assert logits.shape == (2, 16, 97)
        assert cache == {}
