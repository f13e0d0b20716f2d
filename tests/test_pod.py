"""repro.pod: virtual pod topology, the health registry, PodLadder's
cross-pod rungs (compressed gradients + error-feedback threading), the
diversity-bound signal/combinator, and degrade-don't-restart supervision."""

import contextlib
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.adapt import (
    AdaptationProgram,
    BoundedRung,
    Clock,
    Decision,
    FixedPolicy,
    PolicyBase,
    Signals,
    read_signals,
)
from repro.data import sigmoid_synthetic
from repro.dist.plan import ShardingPlan, make_mesh, use_plan
from repro.models import small
from repro.obs.runlog import RunLog, read_runlog
from repro.optim import sgd
from repro.pod import PodHealth, PodLadder, PodTopology
from repro.train import StepEngine, init_state
from repro.train.loop import ModelFns, Trainer
from _hypothesis_compat import given, settings, strategies as st

SEED, N, D = 3, 2048, 32


def _fns():
    return ModelFns(
        batch_loss=small.mlp_batch_loss,
        example_loss=small.mlp_loss,
        metrics=lambda p, b: {"acc": small.mlp_accuracy(p, b)},
    )


def _program(m0=128, m_max=1024):
    return AdaptationProgram(FixedPolicy(m0, m_max, granule=16), base_lr=0.5)


def _trainer(elastic=None, estimator="exact", **kw):
    train, val, _ = sigmoid_synthetic(n=N, d=D, seed=SEED)
    return Trainer(_fns(), small.mlp_init(jax.random.key(SEED), D),
                   sgd(momentum=0.9), _program(), train, val,
                   estimator=estimator, seed=SEED, elastic=elastic, **kw)


# ---------------------------------------------------------------------------
# PodTopology / PodHealth
# ---------------------------------------------------------------------------


class TestPodTopology:
    def test_partitions_contiguous_prefix_pods(self):
        devs = jax.devices()
        topo = PodTopology(2)
        assert len(topo) == topo.num_pods == 2
        assert topo.devices_per_pod == 4
        assert topo.pods[0] == devs[:4] and topo.pods[1] == devs[4:]
        assert topo.pod_of(devs[0]) == 0 and topo.pod_of(devs[5]) == 1

    def test_uneven_partition_raises(self):
        with pytest.raises(ValueError, match="partition"):
            PodTopology(3)  # 8 devices / 3 pods

    def test_bad_pod_count_raises(self):
        with pytest.raises(ValueError, match=">= 1"):
            PodTopology(0)
        with pytest.raises(ValueError, match="partition"):
            PodTopology(16)  # more pods than devices

    def test_foreign_device_raises(self):
        topo = PodTopology(2, jax.devices()[:4])
        with pytest.raises(ValueError, match="not in this topology"):
            topo.pod_of(jax.devices()[7])


class TestPodHealth:
    def test_prefix_semantics(self):
        h = PodHealth(4)
        assert h.healthy_prefix == 4 and h.lost == []
        assert all(h.prefix_healthy(k) for k in (1, 2, 3, 4))
        assert not h.prefix_healthy(0) and not h.prefix_healthy(5)
        h.mark_lost(2)
        assert h.prefix_healthy(2) and not h.prefix_healthy(3)
        assert h.healthy_prefix == 2 and h.lost == [2]
        h.mark_healthy(2)
        assert h.prefix_healthy(4)
        h.mark_lost(0)
        assert h.healthy_prefix == 0 and not h.prefix_healthy(1)
        assert repr(h) == "PodHealth(LHHH)"

    def test_out_of_range_raises(self):
        h = PodHealth(2)
        with pytest.raises(ValueError, match="out of range"):
            h.mark_lost(2)
        with pytest.raises(ValueError, match=">= 1"):
            PodHealth(0)


# ---------------------------------------------------------------------------
# PodLadder structure and health-filtered selection
# ---------------------------------------------------------------------------


class TestPodLadder:
    def test_rung_structure_two_pods(self):
        ladder = PodLadder(pods=2, granule=16)
        assert ladder.widths == [1, 2, 4, 8]
        assert [r.pods for r in ladder.rungs] == [1, 1, 1, 2]
        cross = ladder.rungs[3]
        assert cross.plan.dp == ("pod", "data")
        assert cross.plan.fsdp == ()  # params replicated on cross-pod rungs
        assert dict(cross.plan.mesh.shape) == {"pod": 2, "data": 4}

    def test_rungs_are_device_prefixes(self):
        ladder = PodLadder(pods=2, granule=1)
        ids = [[d.id for d in r.plan.mesh.devices.flat] for r in ladder.rungs]
        for narrow, wide in zip(ids, ids[1:]):
            assert wide[: len(narrow)] == narrow

    def test_four_pods_pow2_cross_rungs(self):
        ladder = PodLadder(pods=4, granule=1)
        # base ladder over pod 0's 2 devices, then 2-pod and 4-pod rungs
        assert ladder.widths == [1, 2, 4, 8]
        assert [r.pods for r in ladder.rungs] == [1, 1, 2, 4]

    def test_single_pod_raises(self):
        with pytest.raises(ValueError, match="pods >= 2"):
            PodLadder(pods=1)

    def test_rung_for_batch_is_health_filtered(self):
        ladder = PodLadder(pods=2, granule=16)
        assert ladder.rung_for_batch(128).index == 3
        assert ladder.rung_for_batch(64).index == 2
        ladder.health.mark_lost(1)
        assert ladder.rung_for_batch(128).index == 2  # cross rung filtered out
        ladder.health.mark_healthy(1)
        assert ladder.rung_for_batch(128).index == 3
        ladder.health.mark_lost(0)
        with pytest.raises(RuntimeError, match="pod 0"):
            ladder.rung_for_batch(128)

    def test_adapt_state_threads_error_feedback(self):
        ladder = PodLadder(pods=2, granule=16)
        state = init_state(small.logreg_init(jax.random.key(0), D), sgd())
        assert state.err_state is None
        cross, within = ladder.rungs[3], ladder.rungs[2]

        # cross-pod: freshly-zeroed stacked (pods, *shape) residuals
        s1 = ladder.adapt_state(state, None, cross)
        shapes = [x.shape for x in jax.tree.leaves(s1.err_state)]
        assert all(s[0] == 2 for s in shapes)
        assert [s[1:] for s in shapes] == [
            jnp.shape(p) for p in jax.tree.leaves(state.params)]
        # same pod layout: residuals survive untouched
        assert ladder.adapt_state(s1, cross, cross) is s1
        # within-pod: residuals dropped
        assert ladder.adapt_state(s1, cross, within).err_state is None
        # changed pod layout: re-zeroed, not carried
        dirty = s1._replace(err_state=jax.tree.map(
            lambda e: e + 1.0, s1.err_state))
        back = ladder.adapt_state(dirty, within, cross)
        assert all(float(jnp.abs(e).max()) == 0.0
                   for e in jax.tree.leaves(back.err_state))

    def test_uncompressed_ladder_carries_no_residuals(self):
        ladder = PodLadder(pods=2, granule=16, compress=False)
        state = init_state(small.logreg_init(jax.random.key(0), D), sgd())
        assert ladder.adapt_state(state, None, ladder.rungs[3]).err_state is None


# ---------------------------------------------------------------------------
# Signals.diversity_bound + BoundedRung
# ---------------------------------------------------------------------------


class _StubPolicy(PolicyBase):
    """Emits one fixed Decision at every boundary."""

    def __init__(self, decision):
        super().__init__()
        self.decision = decision
        self._m = decision.batch_size or 16

    def _decide(self, signals, clock):
        return self.decision

    @property
    def batch_size(self):
        return self._m

    def set_batch_size(self, m):
        self._m = int(m)


def test_diversity_bound_rides_the_stacked_read():
    """The bound is samples * diversity off the SAME transfer as gns —
    populated whenever the window is, zero after the boundary reset."""
    train, _, _ = sigmoid_synthetic(n=512, d=16, seed=0)
    fns = ModelFns(batch_loss=small.logreg_batch_loss,
                   example_loss=small.logreg_loss)
    eng = StepEngine.for_model_fns(fns, sgd(), estimator="moment",
                                   donate=False)
    state = init_state(small.logreg_init(jax.random.key(0), 16), sgd())
    for i in range(3):
        batch = {k: jnp.asarray(v) for k, v in
                 train.get(np.arange(i * 64, (i + 1) * 64)).items()}
        state, _ = eng.step(state, batch, 0.1)
    sig, state = read_signals(state, "moment", reset=False, batch_size=64)
    assert sig.samples == 192.0 and sig.diversity > 0
    assert sig.diversity_bound == pytest.approx(sig.samples * sig.diversity,
                                                rel=1e-6)
    # the epoch-boundary read resets the window; the next read is empty
    sig2, state = read_signals(state, "moment", reset=True, batch_size=64)
    assert sig2.diversity_bound == pytest.approx(sig.diversity_bound, rel=1e-6)
    sig3, _ = read_signals(state, "moment", reset=False, batch_size=64)
    assert sig3.samples == 0.0 and sig3.diversity_bound == 0.0


class TestBoundedRung:
    def _observe(self, decision, bound, **kw):
        pol = BoundedRung(_StubPolicy(decision), **kw)
        return pol.observe(Signals(diversity_bound=bound), Clock(0, 0))

    @settings(max_examples=60)
    @given(bound=st.floats(0.5, 5000.0), granule=st.integers(1, 32),
           m=st.integers(1, 4096))
    def test_never_emits_batch_above_bound(self, bound, granule, m):
        d = self._observe(Decision(batch_size=m, reason="stub"), bound,
                          granule=granule)
        if m <= bound:
            assert d.batch_size == m and d.reason == "stub"
        else:
            expect = granule
            while expect * 2 <= bound:
                expect *= 2
            assert d.batch_size == expect
            # on the lattice, under the cap unless floored at the granule
            assert d.batch_size <= max(granule, bound)
            assert d.reason == "stub+bound"

    @settings(max_examples=40)
    @given(bound=st.floats(0.5, 16.0), rung=st.integers(0, 3))
    def test_never_emits_rung_above_bound(self, bound, rung):
        ladder = PodLadder(pods=2, granule=16)
        d = self._observe(Decision(rung=rung, reason="stub"), bound,
                          ladder=ladder)
        dp = ladder.rungs[d.rung].dp
        assert dp <= bound or d.rung == 0  # narrowest rung is the floor

    def test_clamp_writes_back_into_inner(self):
        inner = _StubPolicy(Decision(batch_size=1024, reason="stub"))
        pol = BoundedRung(inner, granule=16)
        d = pol.observe(Signals(diversity_bound=100.0), Clock(0, 0))
        assert d.batch_size == 64  # largest 16 * 2^k <= 100
        assert inner.batch_size == 64  # inner state agrees with what runs

    def test_missing_or_degenerate_bound_passes_through(self):
        dec = Decision(batch_size=4096, rung=3, reason="stub")
        for bound in (None, 0.0, -1.0, float("inf"), float("nan")):
            d = self._observe(dec, bound, granule=16,
                              ladder=PodLadder(pods=2, granule=16))
            assert d is dec

    def test_margin_scales_the_cap(self):
        d = self._observe(Decision(batch_size=1024, reason="stub"), 100.0,
                          granule=16, margin=2.0)
        assert d.batch_size == 128  # largest 16 * 2^k <= 200

    def test_bad_args_raise(self):
        with pytest.raises(ValueError, match="granule"):
            BoundedRung(_StubPolicy(Decision()), granule=0)
        with pytest.raises(ValueError, match="margin"):
            BoundedRung(_StubPolicy(Decision()), margin=0.0)


# ---------------------------------------------------------------------------
# the cross-pod golden trajectory (compression round-trip)
# ---------------------------------------------------------------------------


def _run(mode, epochs=3):
    if mode == "full":
        elastic, ctx = None, use_plan(
            ShardingPlan(mesh=make_mesh((8,), ("data",))))
    else:
        elastic = PodLadder(pods=2, granule=16,
                            compress=(mode == "compressed"))
        ctx = contextlib.nullcontext()
    with ctx:
        t = _trainer(elastic=elastic)
        hist = t.run(epochs, verbose=False)
    return t, hist


def test_golden_cross_pod_matches_full_mesh():
    """A FixedPolicy(128) run sits on the 2-pod rung the whole way; with
    compression off the (pod, data) pmean is arithmetically the full-mesh
    data-parallel mean, so the trajectory matches the fixed dp=8 run to
    reduction-order tolerance.  With int8+EF compression on, the same run
    stays within quantization tolerance — the round-trip loses no training
    signal — and the error-feedback residuals are live, not silently zero."""
    tf_, hf = _run("full")
    tn, hn = _run("uncompressed")
    tc, hc = _run("compressed")
    assert tn.rung.pods == 2 and tc.rung.pods == 2

    for a, b in zip(jax.tree.leaves(tn.state.params),
                    jax.tree.leaves(tf_.state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose([h.val_loss for h in hn],
                               [h.val_loss for h in hf], rtol=1e-4)

    assert tn.state.err_state is None  # uncompressed rungs carry none
    for a, b in zip(jax.tree.leaves(tc.state.params),
                    jax.tree.leaves(tf_.state.params)):
        # near-zero entries make per-element rtol meaningless: bound the
        # quantization drift relative to the tensor's own scale instead
        a, b = np.asarray(a), np.asarray(b)
        assert np.max(np.abs(a - b)) <= 2e-2 * max(np.max(np.abs(b)), 1.0)
    np.testing.assert_allclose([h.val_loss for h in hc],
                               [h.val_loss for h in hf], rtol=1e-2)

    # EF is live: residuals exist, are per-pod, and are nonzero after steps
    # (they also survived 3 epoch_end boundaries — not silently dropped)
    err = tc.state.err_state
    assert err is not None
    leaves = jax.tree.leaves(err)
    assert all(e.shape[0] == 2 for e in leaves)
    assert sum(float(jnp.abs(e).sum()) for e in leaves) > 0


def test_demote_drops_residuals_and_training_continues(tmp_path):
    """Degrade-don't-restart at the Trainer level: losing pod 1 demotes onto
    the widest all-healthy rung, the residuals (meaningless there) drop, and
    the run carries on producing finite losses — no checkpoint involved."""
    t = _trainer(elastic=PodLadder(pods=2, granule=16))
    assert t.rung.index == 3 and t.state.err_state is not None
    t.run(1, verbose=False)
    assert t.state.err_state is not None  # survived the epoch boundary
    t.elastic.health.mark_lost(1)
    src, dst = t.demote(note="pod 1 lost")
    assert (src, dst) == (3, 2)
    assert t.rung.pods == 1 and t.state.err_state is None
    before = t.history[-1].val_loss
    t.run(2, verbose=False)
    assert np.isfinite(t.history[-1].val_loss)
    assert t.history[-1].val_loss <= before  # still learning, post-demotion


# ---------------------------------------------------------------------------
# supervisor: a host loss degrades the ladder, never the checkpoint path
# ---------------------------------------------------------------------------


def test_supervised_pod_loss_demotes_without_restart(tmp_path):
    from repro.launch.supervisor import run_supervised

    run_dir = str(tmp_path / "run")
    log = RunLog(run_dir, meta={"cmd": "test-pod"})
    train, val, _ = sigmoid_synthetic(n=N, d=D, seed=SEED)

    def make_trainer(mgr):
        return Trainer(_fns(), small.mlp_init(jax.random.key(SEED), D),
                       sgd(momentum=0.9), _program(), train, val,
                       estimator="exact", seed=SEED, ckpt=mgr,
                       elastic=PodLadder(pods=2, granule=16))

    hist = run_supervised(make_trainer, 4, [], str(tmp_path / "ckpt"),
                          runlog=log, lose_pod=[(2, 1)])
    log.close()
    assert len(hist) == 4  # every epoch completed

    ev = read_runlog(run_dir)
    # zero checkpoint restores: one initial start, no restart events after
    restarts = [e for e in ev if e["kind"] == "restart"]
    assert [e["restarts"] for e in restarts] == [0]
    (lost,) = [e for e in ev if e["kind"] == "pod_lost"]
    assert lost["pod"] == 1 and lost["epoch"] == 2 and lost["rung"] == 3
    (dem,) = [e for e in ev if e["kind"] == "demote"]
    assert dem["src"] == 3 and dem["dst"] == 2 and dem["src"] > dem["dst"]
    assert dem["pods"] == 1 and dem["dp"] == 4
    # the run RESUMED on the shrunk rung: epochs after the loss exist and
    # the monitor's schedule reconstruction lands on the demoted rung
    assert sum(e["kind"] == "epoch" for e in ev) == 4
    from repro.launch import monitor

    sched = monitor.schedule(ev)
    assert sched[-1]["rung"] == 2
    assert "demote" in monitor.lifecycle(ev)


def test_supervised_lose_pod_without_pod_ladder_raises(tmp_path):
    from repro.launch.supervisor import run_supervised

    train, val, _ = sigmoid_synthetic(n=256, d=16, seed=0)
    fns = ModelFns(batch_loss=small.logreg_batch_loss)

    def make_trainer(mgr):
        return Trainer(fns, small.logreg_init(jax.random.key(0), 16), sgd(),
                       _program(16, 256), train, val, estimator="none",
                       ckpt=mgr)

    with pytest.raises(ValueError, match="PodLadder"):
        run_supervised(make_trainer, 2, [], str(tmp_path / "ckpt"),
                       lose_pod=[1])


@pytest.mark.slow
def test_supervisor_cli_multi_pod_demotion(tmp_path):
    """End to end in a fresh process: the CLI brings up a 2-pod ladder, a
    --lose-pod injection mid-run demotes (never restarts), and the run log
    written by the child proves it."""
    runlog = str(tmp_path / "runlog.jsonl")
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.supervisor", "--devices", "8",
         "--epochs", "4", "--pods", "2", "--lose-pod", "2",
         "--ckpt-dir", str(tmp_path / "ckpt"), "--runlog", runlog],
        capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": "src"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=300,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    assert "completed 4 epochs" in res.stdout
    ev = read_runlog(runlog)
    assert [e["restarts"] for e in ev if e["kind"] == "restart"] == [0]
    (dem,) = [e for e in ev if e["kind"] == "demote"]
    assert dem["src"] > dem["dst"]
    assert sum(e["kind"] == "epoch" for e in ev) == 4
