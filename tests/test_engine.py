"""StepEngine: bucketed compile cache, donation, golden trajectory vs the
pre-engine host loop, and the in-jit diversity tiers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AdaptiveBatchController, diversity, make_policy
from repro.core.batch_policy import num_buckets
from repro.data import EpochLoader, sigmoid_synthetic
from repro.models import small
from repro.optim import apply_updates, sgd
from repro.train import StepEngine, init_state, make_train_step
from repro.train.loop import EpochRecord, ModelFns, Trainer
from repro.train.step import _to_micro

SEED, N, D = 3, 2048, 32


def _fns():
    return ModelFns(
        batch_loss=small.mlp_batch_loss,
        example_loss=small.mlp_loss,
        metrics=lambda p, b: {"acc": small.mlp_accuracy(p, b)},
        probe_loss=small.mlp_batch_loss_with_probes,
        probe_specs=small.mlp_probe_specs,
    )


def _controller(delta=0.08, m0=32, m_max=256):
    return AdaptiveBatchController(
        make_policy("divebatch", m0=m0, m_max=m_max, delta=delta,
                    dataset_size=N, granule=16),
        base_lr=0.5,
    )


def _reference_run(fns, train, epochs):
    """The pre-engine Trainer loop STRUCTURE: one host-side jit per batch
    (`value_and_grad` + update), separate psn/accumulate jits, no donation,
    per-step host round-trips. One deliberate semantic difference from the
    deleted loop: per-sample norms are evaluated at the same params the
    gradient used (the paper's Delta_S(theta): numerator and denominator
    share theta), where the old loop evaluated exact/gram psn at POST-update
    params, inconsistently with its own moment tier. The engine's in-jit
    tiers use the consistent pre-update theta, so this reference pins the
    engine's (corrected) semantics — see CHANGES.md."""
    params = small.mlp_init(jax.random.key(SEED), D)
    opt = sgd(momentum=0.9)
    opt_state = opt.init(params)
    div = diversity.init_state(params)
    ctrl = _controller()

    @jax.jit
    def sgd_step(p, o, b, lr):
        loss, grads = jax.value_and_grad(fns.batch_loss)(p, b)
        updates, o = opt.update(grads, o, p, lr)
        return apply_updates(p, updates), o, loss, grads

    psn_fn = jax.jit(
        lambda p, b: jnp.sum(diversity.persample_sq_norms(fns.example_loss, p, b))
    )
    acc_fn = jax.jit(diversity.accumulate)

    sizes = []
    for ep in range(epochs):
        bsz, lr = ctrl.batch_size, jnp.float32(ctrl.lr)
        for batch_np in EpochLoader(train, bsz, epoch=ep, seed=SEED):
            batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
            psn = psn_fn(params, batch)
            params, opt_state, _, grads = sgd_step(params, opt_state, batch, lr)
            div = acc_fn(div, grads, bsz, psn)
        decision = ctrl.on_epoch_end(float(diversity.diversity_exact(div)))
        div = diversity.reset_state(div)
        sizes.append(decision.batch_size)
    return params, sizes


def test_golden_trajectory_bit_identical_across_buckets():
    """A DiveBatch run resizing across >=3 buckets through the engine must
    produce bit-identical params to the pre-engine host loop, with the
    compile count bounded by the bucket-lattice size."""
    train, val, _ = sigmoid_synthetic(n=N, d=D, seed=SEED)
    fns = _fns()
    ref_params, ref_sizes = _reference_run(fns, train, epochs=6)

    ctrl = _controller()
    t = Trainer(fns, small.mlp_init(jax.random.key(SEED), D), sgd(momentum=0.9),
                ctrl, train, val, estimator="exact", seed=SEED)
    hist = t.run(6, verbose=False)

    assert [h.batch_size for h in hist] == ref_sizes
    assert len(set(t.engine.stats.buckets)) >= 3  # genuinely spans >=3 buckets
    for a, b in zip(jax.tree.leaves(ref_params), jax.tree.leaves(t.state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # compile bound: <= log2(m_max/granule) + 1, via EngineStats
    assert t.engine.stats.compiles <= ctrl.compile_bound
    assert t.engine.stats.compiles == len(set(t.engine.stats.buckets))
    assert t.engine.stats.donate


def test_bucket_cache_hit_miss_accounting():
    train, val, _ = sigmoid_synthetic(n=512, d=16, seed=0)
    fns = ModelFns(batch_loss=small.logreg_batch_loss,
                   example_loss=small.logreg_loss)
    eng = StepEngine.for_model_fns(fns, sgd(), estimator="moment")
    state = init_state(small.logreg_init(jax.random.key(0), 16), sgd())
    batch = {k: jnp.asarray(v) for k, v in train.get(np.arange(64)).items()}
    state, _ = eng.step(state, batch, 0.1)
    state, _ = eng.step(state, batch, 0.1)
    assert eng.stats.compiles == 1 and eng.stats.bucket_misses == 1
    assert eng.stats.bucket_hits == 1 and eng.stats.steps == 2
    big = {k: jnp.asarray(v) for k, v in train.get(np.arange(128)).items()}
    state, _ = eng.step(state, big, 0.1)
    assert eng.stats.compiles == 2 and eng.stats.buckets == [64, 128]
    # returning to a seen bucket never recompiles
    batch = {k: jnp.asarray(v) for k, v in train.get(np.arange(64)).items()}
    state, _ = eng.step(state, batch, 0.1)
    assert eng.stats.compiles == 2 and eng.stats.bucket_hits == 2


def test_state_buffers_are_donated():
    train, _, _ = sigmoid_synthetic(n=256, d=16, seed=0)
    fns = ModelFns(batch_loss=small.logreg_batch_loss)
    eng = StepEngine.for_model_fns(fns, sgd(momentum=0.9), estimator="moment")
    state = init_state(small.logreg_init(jax.random.key(0), 16), sgd(momentum=0.9))
    batch = {k: jnp.asarray(v) for k, v in train.get(np.arange(32)).items()}
    old = state
    state, _ = eng.step(state, batch, 0.1)
    # donate_argnums=(0,) aliased the old state's buffers into the output
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(old.params))
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(state.params))
    # and an engine built with donate=False keeps them alive
    eng2 = StepEngine.for_model_fns(fns, sgd(momentum=0.9), estimator="moment",
                                    donate=False)
    old = state
    state, _ = eng2.step(state, batch, 0.1)
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(old.params))


def test_to_micro_rejects_off_lattice_batch():
    x = jnp.zeros((12, 4))
    with pytest.raises(ValueError, match="num_micro bucket 8"):
        _to_micro(x, 8, 1)
    # and through a full step build: batch of 12 cannot split into 8 micros
    step = make_train_step(None, sgd(), num_micro=8,
                           loss_fn=lambda p, b: jnp.sum(p["w"] * b["x"]),
                           diversity_on=False)
    with pytest.raises(ValueError, match="not divisible"):
        jax.eval_shape(step, {"w": x}, {"x": x}, jnp.float32(0.1))


def test_exact_tier_psn_chunking_matches_unchunked():
    """psn_chunk bounds the in-jit vmap width without changing the result
    (the Trainer's psn_microbatch still has its pre-engine meaning)."""
    train, _, _ = sigmoid_synthetic(n=256, d=32, seed=1)
    batch = {k: jnp.asarray(v) for k, v in train.get(np.arange(64)).items()}
    params = small.mlp_init(jax.random.key(1), 32)

    def delta(chunk):
        eng = StepEngine.for_model_fns(_fns(), sgd(), estimator="exact",
                                       donate=False, psn_chunk=chunk)
        state, _ = eng.step(init_state(params, sgd()), batch, 0.0)
        return np.asarray(state.div_state.sq_norm_sum)

    full, chunked = delta(None), delta(16)
    np.testing.assert_allclose(chunked, full, rtol=1e-6)


def test_for_lm_rejects_off_lattice_batch():
    """for_lm's bucket key is shape-exact: a batch not divisible by
    micro_batch must raise, never alias another bucket's executable."""
    eng = StepEngine.for_lm(None, sgd(), micro_batch=32)
    bad = {"tokens": jnp.zeros((48, 8), jnp.int32)}
    with pytest.raises(ValueError, match="micro_batch 32"):
        eng.step(None, bad, 0.1)


def test_epoch_end_host_jits_are_cached():
    from repro.train.step import _estimate_jit, _reset_jit

    assert _estimate_jit("moment") is _estimate_jit("moment")
    assert _reset_jit() is _reset_jit()


def test_num_buckets_lattice_size():
    assert num_buckets(256, 16) == 5   # {16, 32, 64, 128, 256}
    assert num_buckets(16, 16) == 1
    assert num_buckets(2048, 16) == 8
    assert _controller(m0=32, m_max=256).compile_bound == 5


def test_compile_bound_tracks_bucket_mode_and_m_min():
    """The bound must stay a HARD bound for every supported policy config,
    not just the pow2 default."""
    none_mode = AdaptiveBatchController(
        make_policy("divebatch", m0=16, m_max=256, delta=0.5, dataset_size=N,
                    granule=16, bucket_mode="none"),
        base_lr=0.5,
    )
    assert none_mode.compile_bound == 16  # every multiple of 16 up to 256
    off_lattice_min = AdaptiveBatchController(
        make_policy("divebatch", m0=32, m_max=256, delta=0.5, dataset_size=N,
                    granule=16, m_min=24),
        base_lr=0.5,
    )
    # an off-lattice m_min snaps UP to the next lattice point (32), so the
    # bound is exactly the lattice size — no extra clamp bucket
    assert off_lattice_min.compile_bound == 5
    assert off_lattice_min.policy.on_epoch_end(0, 0.0).batch_size == 32


def test_trainer_accepts_injected_engine_without_eval_fn():
    """Trainer owns the ModelFns, so a hand-built engine with no eval_fn must
    still evaluate at epoch boundaries."""
    train, val, _ = sigmoid_synthetic(n=256, d=16, seed=0)
    fns = ModelFns(batch_loss=small.logreg_batch_loss,
                   metrics=lambda p, b: {"acc": small.logreg_accuracy(p, b)})
    bare = StepEngine(
        lambda key: make_train_step(None, sgd(), num_micro=1,
                                    loss_fn=fns.batch_loss, diversity_on=False)
    )
    t = Trainer(fns, small.logreg_init(jax.random.key(0), 16), sgd(),
                AdaptiveBatchController(
                    make_policy("sgd", m0=32, m_max=32, granule=16),
                    base_lr=0.5),
                train, val, estimator="none", engine=bare)
    hist = t.run(1, verbose=False)
    assert np.isfinite(hist[0].val_loss) and "acc" in hist[0].val_metrics


def test_cache_key_includes_full_batch_signature():
    """Two batches with the same leading dim but different trailing shape
    must not share an AOT executable (shape-exact dispatch)."""
    fns = ModelFns(batch_loss=lambda p, b: jnp.mean((b["x"] @ p["w"]) ** 2))
    eng = StepEngine.for_model_fns(fns, sgd(), estimator="moment",
                                   diversity_on=False, donate=False)
    params = {"w": jnp.ones((8, 1))}
    state = init_state(params, sgd())
    state, _ = eng.step(state, {"x": jnp.ones((16, 8))}, 0.0)
    # same leading dim, wider feature dim: recompiles instead of crashing
    params2 = {"w": jnp.ones((12, 1))}
    state2 = init_state(params2, sgd())
    state2, _ = eng.step(state2, {"x": jnp.ones((16, 12))}, 0.0)
    assert eng.stats.compiles == 2 and eng.stats.bucket_hits == 0


def test_estimator_tiers_in_jit_consistent():
    """exact/gram/moment folded inside the jitted step must agree with the
    host-side estimators on identical data (one step, lr=0)."""
    train, _, _ = sigmoid_synthetic(n=256, d=32, seed=1)
    fns = _fns()
    batch = {k: jnp.asarray(v) for k, v in train.get(np.arange(64)).items()}
    params = small.mlp_init(jax.random.key(1), 32)

    deltas = {}
    for est in ("exact", "gram", "moment"):
        eng = StepEngine.for_model_fns(fns, sgd(), estimator=est, donate=False)
        state = init_state(params, sgd())
        state, _ = eng.step(state, batch, 0.0)
        fn = diversity.diversity_moment if est == "moment" else diversity.diversity_exact
        deltas[est] = float(fn(state.div_state))
    ref = float(diversity.diversity_exact(
        diversity.accumulate(
            diversity.init_state(params),
            jax.grad(lambda p: small.mlp_batch_loss(p, batch))(params), 64,
            jnp.sum(diversity.persample_sq_norms(small.mlp_loss, params, batch)),
        )
    ))
    np.testing.assert_allclose(deltas["exact"], ref, rtol=1e-5)
    assert 0.3 < deltas["gram"] / deltas["exact"] < 1.05
    assert deltas["moment"] > 0


def test_estimator_tier_flips_key_the_compile_cache():
    """(bucket, rung, tier) cache: a Decision.estimator flip compiles the
    new tier's bucket once, and flipping BACK onto a seen tier is a cache
    hit, not a recompile (closes the ROADMAP open item)."""
    train, _, _ = sigmoid_synthetic(n=512, d=32, seed=0)
    eng = StepEngine.for_model_fns(_fns(), sgd(), estimator="exact",
                                   donate=False)
    assert eng.tiered and eng.tier == "exact"
    params = small.mlp_init(jax.random.key(0), 32)
    batch = {k: jnp.asarray(v) for k, v in train.get(np.arange(64)).items()}
    state = init_state(params, sgd())
    state, _ = eng.step(state, batch, 0.1)
    eng.tier = "moment"
    state, _ = eng.step(state, batch, 0.1)
    assert eng.stats.compiles == 2
    eng.tier = "exact"
    state, _ = eng.step(state, batch, 0.1)
    assert eng.stats.compiles == 2 and eng.stats.bucket_hits == 1
    assert eng.stats.tiers == ["exact", "moment"]
    # the tier-extended accounting bound
    assert eng.stats.compiles == len(
        set(zip(eng.stats.buckets, eng.stats.rungs, eng.stats.tiers))
    )


def test_trainer_tier_flip_keeps_engine_and_cache():
    """On a tiered engine the Trainer applies a Decision.estimator by
    setting ``engine.tier`` — same engine object, jit family intact."""
    train, val, _ = sigmoid_synthetic(n=512, d=16, seed=0)
    fns = ModelFns(batch_loss=small.logreg_batch_loss,
                   example_loss=small.logreg_loss)
    t = Trainer(fns, small.logreg_init(jax.random.key(0), 16), sgd(),
                _controller(m0=32, m_max=64), train, val, estimator="exact")
    engine = t.engine
    t.run(1, verbose=False)
    jits_before = dict(engine._jits)
    t._apply_estimator("moment")
    assert t.engine is engine and engine.tier == "moment"
    assert t.estimator == "moment"
    for key, fn in jits_before.items():  # old tier's programs stay warm
        assert engine._jits[key] is fn
    t.run(1, verbose=False)
    assert set(engine.stats.tiers) == {"exact", "moment"}


def test_kwargs_build_counts_as_untiered():
    """Only genuinely positional parameters make a build tiered: a
    (key, **opts) build must not be handed a positional tier argument."""
    eng = StepEngine(
        lambda key, **opts: make_train_step(
            None, sgd(), num_micro=1,
            loss_fn=lambda p, b: jnp.sum(p["w"] * b["x"]),
            diversity_on=False)
    )
    assert not eng.tiered
    eng.jitted(1)  # would TypeError if misclassified as tiered


def test_for_lm_names_its_default_tier():
    """for_lm seeds engine.tier with the starting tier so a flip away and
    back is a cache hit, matching for_model_fns."""
    assert StepEngine.for_lm(None, sgd(), micro_batch=32).tier == "moment"
    assert StepEngine.for_lm(None, sgd(), micro_batch=32,
                             diversity_on=False).tier is None


def test_untiered_build_rejects_tier():
    """A hand-built engine whose build takes only (key) cannot honor a tier
    token — setting one must fail loudly, not silently ignore the flip."""
    eng = StepEngine(
        lambda key: make_train_step(None, sgd(), num_micro=1,
                                    loss_fn=lambda p, b: jnp.sum(p["w"]),
                                    diversity_on=False)
    )
    assert not eng.tiered
    eng.tier = "moment"
    with pytest.raises(ValueError, match="tier"):
        eng.jitted(1)


def test_trainer_under_dist_plan_matches_unsharded():
    """The same Trainer/engine code runs under a dist plan (dp-sharded
    batches on the 8-device test mesh) with an equivalent trajectory."""
    from repro.dist.plan import ShardingPlan, make_mesh, use_plan

    train, val, _ = sigmoid_synthetic(n=1024, d=16, seed=0)
    fns = ModelFns(batch_loss=small.logreg_batch_loss,
                   example_loss=small.logreg_loss,
                   metrics=lambda p, b: {"acc": small.logreg_accuracy(p, b)})

    def run(plan):
        ctx = use_plan(plan) if plan else _null()
        with ctx:
            t = Trainer(fns, small.logreg_init(jax.random.key(0), 16),
                        sgd(momentum=0.9), _controller(delta=0.2, m0=32, m_max=128),
                        train, val, estimator="exact", seed=0)
            return t.run(3, verbose=False)

    import contextlib as _ctl
    _null = _ctl.nullcontext
    base = run(None)
    mesh = make_mesh((8,), ("data",))
    sharded = run(ShardingPlan(mesh=mesh))
    assert [h.batch_size for h in base] == [h.batch_size for h in sharded]
    np.testing.assert_allclose([h.val_loss for h in base],
                               [h.val_loss for h in sharded], rtol=1e-4)


def test_estimator_none_with_divebatch_degenerates_gracefully():
    """estimator='none' under a diversity-driven policy must not crash: the
    accumulators are never fed, so the estimate is a legitimate 0.0 (matches
    the pre-engine loop) and the policy collapses to its minimum bucket."""
    train, val, _ = sigmoid_synthetic(n=512, d=16, seed=0)
    fns = ModelFns(batch_loss=small.logreg_batch_loss)
    t = Trainer(fns, small.logreg_init(jax.random.key(0), 16), sgd(),
                _controller(m0=32, m_max=128), train, val, estimator="none")
    hist = t.run(2, verbose=False)
    assert hist[0].diversity == 0.0
    assert hist[-1].batch_size == 16  # bucket(0) -> granule floor


def test_run_logs_zero_diversity(monkeypatch):
    """A legitimate diversity of 0.0 must print as 0, not '-' (None)."""
    train, val, _ = sigmoid_synthetic(n=256, d=16, seed=0)
    fns = ModelFns(batch_loss=small.logreg_batch_loss)
    t = Trainer(fns, small.logreg_init(jax.random.key(0), 16), sgd(),
                _controller(m0=32, m_max=64), train, val, estimator="moment")
    rec = EpochRecord(epoch=0, batch_size=32, lr=0.5, train_loss=1.0,
                      val_loss=1.0, val_metrics={}, diversity=0.0, steps=8,
                      wall_s=0.1)
    lines = []
    monkeypatch.setattr(t, "run_epoch", lambda: rec)
    monkeypatch.setattr("repro.train.loop.log",
                        type("L", (), {"info": lambda *a: lines.append(a[-1])})())
    t.run(1, verbose=True)
    assert lines == ["0"]  # rendered via %s of the formatted diversity
    rec2 = EpochRecord(**{**rec.__dict__, "diversity": None})
    monkeypatch.setattr(t, "run_epoch", lambda: rec2)
    t.run(1, verbose=True)
    assert lines[-1] == "-"


def test_exact_tier_kernel_psn_matches_vmap():
    """psn_impl='kernel' replaces vmap-of-grad per-sample norms with one
    probe-gradient pass through the fused psgn lane. The MLP is
    bias-complete dense (every param sits in a probed kernel or bias), so
    the kernel path is mathematically exact — same sq_norm_sum, same
    trajectory."""
    train, _, _ = sigmoid_synthetic(n=256, d=32, seed=2)
    params = small.mlp_init(jax.random.key(2), 32)

    def run(impl):
        eng = StepEngine.for_model_fns(_fns(), sgd(), estimator="exact",
                                       donate=False, psn_impl=impl)
        state = init_state(params, sgd())
        for lo in (0, 64):
            batch = {k: jnp.asarray(v)
                     for k, v in train.get(np.arange(lo, lo + 64)).items()}
            state, _ = eng.step(state, batch, 0.1)
        return state

    ref, ker = run("vmap"), run("kernel")
    np.testing.assert_allclose(np.asarray(ker.div_state.sq_norm_sum),
                               np.asarray(ref.div_state.sq_norm_sum),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(ref.params), jax.tree.leaves(ker.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_exact_tier_kernel_requires_probes():
    fns = ModelFns(batch_loss=small.mlp_batch_loss,
                   example_loss=small.mlp_loss)
    with pytest.raises(ValueError, match="probe_loss"):
        make_train_step(None, sgd(), num_micro=1, loss_fn=fns.batch_loss,
                        estimator="exact", psn_impl="kernel")
    with pytest.raises(ValueError, match="unknown psn_impl"):
        make_train_step(None, sgd(), num_micro=1, loss_fn=fns.batch_loss,
                        diversity_on=False, psn_impl="pallas?")


def test_for_lm_pallas_matches_dense_trajectory():
    """attn_impl='pallas' routes the training forward AND the recompute
    backward through kernels/attention.flash_attention; the trajectory must
    match the XLA dense path to float tolerance and be deterministic."""
    from repro.configs.base import ModelConfig
    from repro.models import transformer as tf

    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=32,
                      num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=97,
                      param_dtype="float32", compute_dtype="float32",
                      xent_chunk=32, remat=False)
    rng = np.random.default_rng(7)
    toks = rng.integers(0, 97, size=(8, 17), dtype=np.int64)
    batch = {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
             "targets": jnp.asarray(toks[:, 1:], jnp.int32)}
    params = tf.init_params(cfg, jax.random.key(5))

    def run(attn_impl):
        eng = StepEngine.for_lm(cfg, sgd(momentum=0.9), micro_batch=4,
                                donate=False, attn_impl=attn_impl)
        state = init_state(params, sgd(momentum=0.9))
        losses = []
        for _ in range(3):
            state, m = eng.step(state, batch, 0.05)
            losses.append(float(m["loss"]))
        return state, losses

    st_d, loss_d = run(None)
    st_p, loss_p = run("pallas")
    st_p2, loss_p2 = run("pallas")
    assert loss_p == loss_p2  # kernel lane is deterministic
    np.testing.assert_allclose(loss_p, loss_d, rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree.leaves(st_d.params), jax.tree.leaves(st_p.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)
