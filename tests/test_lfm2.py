"""LFM2-MoE through the normal training path, at small widths on the CPU:
the program against the plain reference of the benchmark
(``chipbench/reference/lfm2_moe.py``), the dropless held-share expert layer,
the gated short convolution, the grouped matmul's kernel path against its
fallback, QK-norm attention at head dim 64, the serving refusal, and a guard
that Yi-6B computes bit for bit what it did before LFM2 came in."""

import hashlib
import importlib.util
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import attention as kattn
from repro.kernels import ref as kref
from repro.kernels.gmm import grouped_matmul
from repro.models import conv as conv_lib
from repro.models import moe as moe_lib
from repro.models import transformer as tf
from repro.optim import sgd
from repro.train import StepEngine, init_state

ROOT = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"


def _reference():
    if str(ROOT) not in sys.path:  # the reference imports chipbench's llama helpers
        sys.path.insert(0, str(ROOT))
    path = ROOT / "chipbench" / "reference" / "lfm2_moe.py"
    spec = importlib.util.spec_from_file_location("lfm2_moe_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
KEY = jax.random.key(3)


def _setup(attn_impl="dense", held=8, routed=32, layers=6):
    """A small LFM2 (6 layers: conv, conv, attn, conv, conv, conv; two dense
    FFNs, then experts) as the benchmark's configuration file describes it,
    the program's config, and weights from the reference."""
    config = json.loads((ROOT / "chipbench/configs/lfm2-8b-a1b-train-6l.json").read_text())
    config.update({"hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
                   "num_key_value_heads": 2, "moe_intermediate_size": 32, "vocab_size": 251,
                   "num_hidden_layers": layers, "num_experts": held,
                   "published": {"num_experts": routed}})
    dims = REF.dims_of(config)
    cfg = get_config("lfm2-8b-a1b").replace(
        num_layers=layers, d_model=64, num_heads=4, num_kv_heads=2, d_ff=96,
        d_ff_expert=32, vocab_size=251, num_experts=routed, experts_held=held,
        xent_chunk=64, param_dtype="float32",
        compute_dtype="float32", attn_impl=attn_impl, remat=False)
    return dims, cfg, REF.make_weights(dims, KEY, "float32")


def _batch(b=2, s=64, vocab=251, seed=1):
    toks = jax.random.randint(jax.random.key(seed), (b, s), 0, vocab)
    return {"tokens": toks, "targets": jnp.roll(toks, -1, axis=1)}


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.max(np.abs(b)), 1e-30)
    assert np.max(np.abs(a - b)) <= tol * scale, (np.max(np.abs(a - b)), scale)


@pytest.mark.parametrize("attn_impl", ["dense", "pallas"])
def test_program_loss_and_gradients_match_the_reference(attn_impl):
    dims, cfg, w = _setup(attn_impl)
    batch = _batch()
    with jax.default_matmul_precision("highest"):
        want, g_ref = jax.value_and_grad(
            lambda w: REF.loss(dims, w, batch["tokens"], batch["targets"]))(w)
        (got, metrics), g = jax.value_and_grad(
            lambda w: tf.loss_fn(cfg, w, batch), has_aux=True)(w)
    assert jax.tree.structure(g) == jax.tree.structure(g_ref)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
        _close(a, b, 1e-5)
    # four expert layers route 2 x 64 tokens x top-4 over 32 experts
    assert 0 < int(metrics["moe_rows"]) < 4 * 128 * 4
    assert float(metrics["moe_aux"]) == 0.0


def _layer_params(w, layer=2):
    return jax.tree.map(lambda x: x[0], w[f"pos{layer}"]["ffn"])


def _layer_input(d=64, t=96, seed=4):
    return jax.random.normal(jax.random.key(seed), (1, t, d), jnp.float32)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Experts 0-7, 8-15, 16-23 and 24-31 held on four chips: their parts of
    the layer sum to the reference layer that holds all 32."""
    x = _layer_input()
    dims, _, whole = _setup(held=32)
    p = _layer_params(whole)
    with jax.default_matmul_precision("highest"):
        want = REF._experts(dims, "f32", x, p)
        total = 0.0
        for share in range(4):
            cut = {**p, **{k: p[k][8 * share: 8 * (share + 1)]
                           for k in ("w_gate", "w_up", "w_out")}}
            y, counters = moe_lib.held_moe_apply(cut, x, top_k=4, first=8 * share)
            total = total + y
    _close(total, want, 1e-5)


@pytest.mark.parametrize("interpret", [None, True], ids=["fallback", "kernel"])
def test_nothing_is_dropped_when_every_token_routes_to_one_held_expert(interpret):
    """A bias that puts every token's top-4 on held experts 0-3, so each of
    them takes every token: every assignment is computed."""
    x = _layer_input()
    dims, _, w = _setup(held=8)
    p = _layer_params(w)
    p = {**p, "expert_bias": p["expert_bias"].at[:4].add(10.0)}
    with jax.default_matmul_precision("highest"):
        want = REF._experts(dims, "f32", x, p)
        y, counters = moe_lib.held_moe_apply(p, x, top_k=4, first=0, interpret=interpret)
    assert int(counters["moe_rows"]) == 96 * 4
    assert int(counters["moe_max_expert_rows"]) == 96
    _close(y, want, 1e-5)


@pytest.mark.parametrize("interpret", [None, True], ids=["fallback", "kernel"])
def test_the_row_budget_pads_with_rows_that_change_nothing(interpret, monkeypatch):
    """Zero rows up to the budget (at 4.0, past every held row to the whole
    buffer) give the layer, its counters and its gradients bit for bit as
    visiting the held rows alone (a budget of 0)."""
    x = _layer_input()
    _, _, w = _setup()
    p = _layer_params(w, layer=3)
    budgets = (moe_lib.ROW_BUDGET, 4.0)

    def run(budget):
        monkeypatch.setattr(moe_lib, "ROW_BUDGET", budget)

        def f(p, x):
            y, counters = moe_lib.held_moe_apply(p, x, top_k=4, first=0, interpret=interpret)
            return jnp.sum(jnp.sin(y)), (y, counters)

        return jax.value_and_grad(f, (0, 1), has_aux=True)(p, x)

    want = jax.tree.leaves(run(0.0))
    for budget in budgets:
        for a, b in zip(jax.tree.leaves(run(budget)), want, strict=True):
            assert np.array_equal(np.asarray(a), np.asarray(b))


def test_selection_uses_scores_plus_bias_and_weights_use_scores():
    logits = jnp.array([[2.0, 1.0, 0.0, -1.0]])
    bias = jnp.array([-5.0, 0.0, 0.0, 5.0])
    p = {"router": {"kernel": jnp.eye(4)}, "expert_bias": bias}
    weights, experts = moe_lib.route_sigmoid(p, logits, top_k=2)
    assert sorted(np.asarray(experts[0]).tolist()) == [1, 3]  # not [0, 1]
    s = jax.nn.sigmoid(logits[0])
    want = jnp.array([s[int(e)] for e in experts[0]])
    np.testing.assert_allclose(np.asarray(weights[0]), np.asarray(want / (want.sum() + 1e-6)),
                               rtol=1e-6)


def test_short_conv_is_causal_and_matches_the_reference():
    dims, cfg, w = _setup()
    p = jax.tree.map(lambda x: x[0], w["pos0"]["conv"])
    x = _layer_input(t=32)
    with jax.default_matmul_precision("highest"):
        y = conv_lib.short_conv_apply(p, x)
        _close(y, REF._conv(dims, "f32", x, p), 1e-6)
        t = 20
        y2 = conv_lib.short_conv_apply(p, x.at[:, t].add(1.0))
    moved = np.any(np.asarray(y2 != y), axis=-1)[0]
    assert not moved[:t].any() and moved[t:t + 3].all()


def test_gmm_kernel_matches_the_fallback():
    """The megablox kernels under the custom VJP (interpret mode) against
    ``ragged_dot``: forward and both gradients, an empty group, and rows past
    the groups, which only the mask below decides."""
    r = np.random.default_rng(0)
    m, k, n = 256, 128, 256
    x = jnp.asarray(r.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(r.standard_normal((4, k, n)), jnp.float32)
    sizes = jnp.array([30, 0, 100, 60], jnp.int32)
    valid = (jnp.arange(m) < 190)[:, None]

    def f(x, w, interpret):
        y = jnp.where(valid, grouped_matmul(jnp.where(valid, x, 0), w, sizes, interpret), 0)
        return jnp.sum(jnp.sin(y)), y

    (_, y_k), g_k = jax.value_and_grad(f, (0, 1), has_aux=True)(x, w, True)
    (_, y_f), g_f = jax.value_and_grad(f, (0, 1), has_aux=True)(x, w, None)
    _close(y_k, y_f, 1e-5)
    for a, b in zip(g_k, g_f):
        assert np.all(np.isfinite(np.asarray(a)))
        _close(a, b, 1e-5)


def test_held_layer_kernel_path_matches_its_fallback():
    x = _layer_input()
    _, _, w = _setup()
    p = _layer_params(w, layer=3)

    def f(p, x, interpret):
        y, _ = moe_lib.held_moe_apply(p, x, top_k=4, first=0, interpret=interpret)
        return jnp.sum(jnp.sin(y))

    gk = jax.grad(f, (0, 1))(p, x, True)
    gf = jax.grad(f, (0, 1))(p, x, None)
    for a, b in zip(jax.tree.leaves(gk), jax.tree.leaves(gf)):
        assert np.all(np.isfinite(np.asarray(a)))
        _close(a, b, 1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_kernel_at_head_dim_64_matches_dense(causal):
    r = np.random.default_rng(1)
    q, k, v = (jnp.asarray(r.standard_normal((1, 96, h, 64)), jnp.float32) for h in (4, 2, 2))
    got = kattn.flash_attention(q, k, v, causal, None, None, 32, 32, True)
    want = kref.flash_ref(q, k, v, causal=causal, window=None, softcap=None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))

    gk = jax.grad(loss(lambda *a: kattn.flash_attention(*a, causal, None, None, 32, 32, True)),
                  (0, 1, 2))(q, k, v)
    gr = jax.grad(loss(lambda *a: kref.flash_ref(*a, causal=causal, window=None, softcap=None)),
                  (0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


def test_expert_bias_is_unchanged_after_three_steps():
    _, cfg, w = _setup()
    opt = sgd(momentum=0.9)
    state = init_state(w, opt)
    bias0 = {k: np.asarray(v["ffn"]["expert_bias"]) for k, v in w.items()
             if k.startswith("pos") and "expert_bias" in v["ffn"]}
    router0 = np.asarray(w["pos2"]["ffn"]["router"]["kernel"])  # w is donated
    engine = StepEngine.for_lm(cfg, opt, micro_batch=2)
    for i in range(3):
        state, metrics = engine.step(state, _batch(b=4, seed=10 + i), 0.05)
    assert set(metrics) >= {"loss", "moe_rows", "moe_max_expert_rows"}
    assert int(metrics["moe_max_expert_rows"]) <= int(metrics["moe_rows"])
    for k, b in bias0.items():
        assert np.array_equal(np.asarray(state.params[k]["ffn"]["expert_bias"]), b)
        assert not np.any(np.asarray(state.opt_state.momentum[k]["ffn"]["expert_bias"]))
    assert not np.array_equal(np.asarray(state.params["pos2"]["ffn"]["router"]["kernel"]),
                              router0)


def test_yi_loss_and_gradients_are_as_recorded():
    """The reduced Yi-6B's loss and every gradient leaf, bit for bit, as the
    program computed them before LFM2's settings came in (recorded in
    tests/data/yi_guard.json): its defaults reproduce today's program."""
    cfg = get_config("yi-6b", reduced=True)
    params = tf.init_params(cfg, jax.random.key(7))
    toks = jax.random.randint(jax.random.key(8), (2, 64), 0, cfg.vocab_size)
    batch = {"tokens": toks, "targets": jnp.roll(toks, -1, axis=1)}
    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda p: tf.loss_fn(cfg, p, batch), has_aux=True))(params)
    got = {"loss": float(loss).hex(),
           "grads": {jax.tree_util.keystr(k): hashlib.sha256(np.asarray(v).tobytes()).hexdigest()
                     for k, v in jax.tree.flatten_with_path(grads)[0]}}
    assert got == json.loads((DATA / "yi_guard.json").read_text())


def test_serving_refuses_short_convolutions():
    from repro.serve import ServeEngine

    cfg = get_config("lfm2-8b-a1b", reduced=True)
    with pytest.raises(NotImplementedError, match="Reach 3"):
        tf.init_cache(cfg, 2, 16)
    with pytest.raises(NotImplementedError, match="Reach 3"):
        ServeEngine(cfg, tf.param_specs(cfg))


def test_a_cut_keeps_the_first_layers_of_the_period():
    cfg = get_config("lfm2-8b-a1b").replace(num_layers=6)
    assert cfg.pattern == ("conv", "conv", "attn", "conv", "conv", "conv")
    assert cfg.ffn_pattern == ("dense", "dense", "moe", "moe", "moe", "moe")
    assert cfg.repeats == 1
