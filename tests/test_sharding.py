"""PartitionSpec inference rules — pure logic, no devices required beyond 1.

Builds a fake multi-axis Mesh cheaply via an AbstractMesh so divisibility
resolution can be tested against the production (16,16)/(2,16,16) shapes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config
from repro.dist.plan import ShardingPlan
from repro.dist.sharding import _fit_axes, batch_pspecs, cache_pspecs, infer_pspecs
from repro.models import transformer as tf


def _plan(multi=False):
    if multi:
        mesh = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
        return ShardingPlan(mesh=mesh, dp=("pod", "data"), fsdp=("pod", "data"),
                            tp="model", ep=("pod", "data"))
    mesh = AbstractMesh((16, 16), ("data", "model"))
    return ShardingPlan(mesh=mesh, dp=("data",), fsdp=("data",), tp="model",
                        ep=("data",))


def _find(pspecs, path_frag):
    from repro.utils import pytree as ptu

    out = {}
    flat = ptu.tree_flatten_with_paths(
        jax.tree.map(lambda x: x, pspecs, is_leaf=lambda x: isinstance(x, P))
    )
    for path, leaf in flat:
        if path_frag in path:
            out[path] = leaf
    return out


class TestParamRules:
    def test_dense_arch_rules(self):
        cfg = get_config("qwen2-7b")
        specs = tf.param_specs(cfg)
        ps = infer_pspecs(specs, _plan())
        qk = _find(ps, "attn/q/kernel")
        assert list(qk.values())[0] == P(None, "data", "model")  # (R, d, H*hd)
        ok = _find(ps, "attn/o/kernel")
        assert list(ok.values())[0] == P(None, "model", "data")
        lm = _find(ps, "lm_head/kernel")
        assert list(lm.values())[0] == P(None, "model")  # d replicated, V tp
        norm = _find(ps, "final_norm/scale")
        assert list(norm.values())[0] == P(None)

    def test_vocab_not_divisible_stays_replicated(self):
        cfg = get_config("internvl2-1b")  # vocab 151655 (odd)
        specs = tf.param_specs(cfg)
        ps = infer_pspecs(specs, _plan())
        lm = list(_find(ps, "lm_head/kernel").values())[0]
        assert lm == P(None, None)

    def test_moe_expert_parallel(self):
        cfg = get_config("kimi-k2-1t-a32b")
        specs = tf.param_specs(cfg)
        ps = infer_pspecs(specs, _plan())
        wg = list(_find(ps, "ffn/w_gate").values())[0]
        assert wg == P(None, "data", None, "model")  # (R, E, d, ff)

    def test_moe_ep_over_pod_multi(self):
        cfg = get_config("kimi-k2-1t-a32b")
        specs = tf.param_specs(cfg)
        ps = infer_pspecs(specs, _plan(multi=True))
        wg = list(_find(ps, "ffn/w_gate").values())[0]
        assert wg == P(None, ("pod", "data"), None, "model")

    def test_mamba_channel_tp(self):
        cfg = get_config("falcon-mamba-7b")
        specs = tf.param_specs(cfg)
        ps = infer_pspecs(specs, _plan())
        a = list(_find(ps, "mamba/A_log").values())[0]
        assert a == P(None, "model", None)  # (R, d_inner, ds)

    def test_state_trees_shard_like_params(self):
        """momentum / grad_sum leaves match the param rules by suffix."""
        from repro.optim import sgd
        from repro.train.state import init_state

        cfg = get_config("yi-6b", reduced=True).replace(
            d_model=64, num_heads=4, num_kv_heads=2)
        specs = jax.eval_shape(
            lambda k: init_state(tf.init_params(cfg, k), sgd(momentum=0.9)),
            jax.random.key(0),
        )
        ps = infer_pspecs(specs, _plan())
        mom = _find(ps, "momentum/pos0/attn/q/kernel")
        par = _find(ps, "params/pos0/attn/q/kernel")
        assert list(mom.values())[0] == list(par.values())[0]
        div = _find(ps, "grad_sum/pos0/attn/q/kernel")
        assert list(div.values())[0] == list(par.values())[0]


class TestBatchCacheRules:
    def test_batch_sharded_over_dp(self):
        specs = {"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32)}
        ps = batch_pspecs(specs, _plan(multi=True))
        assert ps["tokens"] == P(("pod", "data"), None)

    def test_batch_indivisible_replicated(self):
        specs = {"tokens": jax.ShapeDtypeStruct((3, 128), jnp.int32)}
        ps = batch_pspecs(specs, _plan())
        assert ps["tokens"] == P(None, None)

    def test_kv_cache_rules(self):
        cache = {
            "pos0": {
                "k": jax.ShapeDtypeStruct((32, 128, 32768, 8, 128), jnp.bfloat16),
                "v": jax.ShapeDtypeStruct((32, 128, 32768, 8, 128), jnp.bfloat16),
            },
            "len": jax.ShapeDtypeStruct((), jnp.int32),
        }
        ps = cache_pspecs(cache, _plan())
        # batch shards over data; kv=8 not divisible by 16 -> head_dim takes tp
        assert ps["pos0"]["k"] == P(None, "data", None, None, "model")

    def test_long_context_batch1_shards_sequence(self):
        cache = {
            "pos0": {
                "k": jax.ShapeDtypeStruct((4, 1, 524288, 8, 128), jnp.bfloat16),
                "v": jax.ShapeDtypeStruct((4, 1, 524288, 8, 128), jnp.bfloat16),
            },
        }
        ps = cache_pspecs(cache, _plan())
        assert ps["pos0"]["k"] == P(None, None, "data", None, "model")


class TestFitAxes:
    """Divisibility resolution: the largest-product axis subset that divides
    the dim wins; anything indivisible degrades to replication (None)."""

    def test_exact_single_axis(self):
        assert _fit_axes(32, ("data",), _plan()) == "data"

    def test_prime_dim_replicates(self):
        assert _fit_axes(7, ("data",), _plan()) is None
        assert _fit_axes(151655, ("data",), _plan()) is None  # odd vocab

    def test_prime_dim_multi_axis_replicates(self):
        assert _fit_axes(3, ("pod", "data"), _plan(multi=True)) is None

    def test_dim_smaller_than_axis_product(self):
        plan = _plan(multi=True)  # pod=2, data=16 -> product 32
        # 16 < 32: the 16-way 'data' axis alone divides and beats 'pod'
        assert _fit_axes(16, ("pod", "data"), plan) == "data"
        # 8: only the 2-way 'pod' axis divides
        assert _fit_axes(8, ("pod", "data"), plan) == "pod"
        # 2: exactly the pod axis
        assert _fit_axes(2, ("pod", "data"), plan) == "pod"

    def test_multi_axis_factorization(self):
        plan = _plan(multi=True)
        assert _fit_axes(64, ("pod", "data"), plan) == ("pod", "data")
        assert _fit_axes(256, ("pod", "data"), plan) == ("pod", "data")

    def test_zero_and_one_replicate(self):
        plan = _plan()
        assert _fit_axes(1, ("data",), plan) is None
        assert _fit_axes(0, ("data",), plan) is None

    def test_string_axes_accepted(self):
        assert _fit_axes(64, "model", _plan()) == "model"
        assert _fit_axes(9, "model", _plan()) is None
