"""repro.elastic: the mesh ladder, exact resharding, the (bucket, rung)
compile cache, cross-rung checkpoint round-trips, and the golden elastic
trajectory vs the fixed-full-mesh run."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, strategies as st

from repro.ckpt import CheckpointManager
from repro.core import AdaptiveBatchController, make_policy
from repro.core.batch_policy import num_buckets
from repro.data import sigmoid_synthetic
from repro.dist.plan import ShardingPlan, make_mesh, use_plan
from repro.elastic import MeshLadder, place, reshard, same_plan
from repro.models import small
from repro.optim import sgd
from repro.train import init_state
from repro.train.loop import ModelFns, Trainer

SEED, N, D = 3, 2048, 32


def _fns():
    return ModelFns(
        batch_loss=small.mlp_batch_loss,
        example_loss=small.mlp_loss,
        metrics=lambda p, b: {"acc": small.mlp_accuracy(p, b)},
    )


def _controller(m0=16, m_max=256, delta=0.08, granule=16):
    return AdaptiveBatchController(
        make_policy("divebatch", m0=m0, m_max=m_max, delta=delta,
                    dataset_size=N, granule=granule),
        base_lr=0.5,
    )


# ---------------------------------------------------------------------------
# MeshLadder
# ---------------------------------------------------------------------------


class TestMeshLadder:
    def test_pow2_rungs_over_test_mesh(self):
        ladder = MeshLadder(granule=16)  # the 8-device conftest harness
        assert ladder.widths == [1, 2, 4, 8]
        assert ladder.num_rungs == 4
        assert ladder.full.dp == 8

    def test_rung_devices_are_nested_prefixes(self):
        ladder = MeshLadder(granule=1)
        ids = [
            [d.id for d in r.plan.mesh.devices.flat] for r in ladder
        ]
        for narrow, wide in zip(ids, ids[1:]):
            assert wide[: len(narrow)] == narrow

    def test_plan_for_batch_keeps_granule_per_device(self):
        ladder = MeshLadder(granule=16)
        assert ladder.rung_for_batch(16).dp == 1
        assert ladder.rung_for_batch(32).dp == 2
        assert ladder.rung_for_batch(64).dp == 4
        assert ladder.rung_for_batch(128).dp == 8
        assert ladder.rung_for_batch(256).dp == 8  # tops out at the mesh
        assert ladder.plan_for_batch(64).dp_size == 4

    def test_sub_granule_batch_runs_narrowest_rung(self):
        ladder = MeshLadder(granule=16)
        assert ladder.rung_for_batch(8).dp == 1
        assert ladder.rung_for_batch(13).dp == 1  # indivisible too

    def test_model_axes_held_fixed(self):
        ladder = MeshLadder(granule=1, model_axes=(("model", 2),))
        assert ladder.widths == [1, 2, 4]
        for rung in ladder:
            assert rung.plan.mesh.shape["model"] == 2
            assert rung.plan.tp_size == 2
        assert ladder.rung_for_batch(4).dp == 4
        assert ladder.full.devices == 8

    def test_explicit_dp_widths(self):
        ladder = MeshLadder(granule=1, dp_widths=[1, 8])
        assert ladder.widths == [1, 8]
        assert ladder.rung_for_batch(4).dp == 1  # 8 does not divide 4

    def test_too_few_devices_for_model_axes_raises(self):
        with pytest.raises(ValueError, match="cannot carry"):
            MeshLadder(jax.devices()[:1], model_axes=(("model", 2),))

    @settings(max_examples=24)
    @given(ndev=st.integers(1, 8), granule=st.integers(1, 32))
    def test_default_dp_widths_property(self, ndev, granule):
        """For ANY device count (non-pow2 included) the default widths are a
        sorted deduped pow2 chain topped by the device count, every rung's
        devices are a prefix of the flat list, and the selected dp width is
        monotone non-decreasing over the batch lattice m = granule * 2^k."""
        ladder = MeshLadder(jax.devices()[:ndev], granule=granule)
        widths = ladder.widths
        assert widths == sorted(set(widths)) and widths[-1] == ndev
        pow2 = [1 << i for i in range(ndev.bit_length()) if 1 << i <= ndev]
        assert [w for w in widths if w & (w - 1) == 0] == pow2
        assert all(w in pow2 or w == ndev for w in widths)
        for r in ladder:
            assert [d.id for d in r.plan.mesh.devices.flat] == \
                   [d.id for d in jax.devices()[: r.dp]]
        dps = [ladder.rung_for_batch(granule << k).dp for k in range(8)]
        assert dps == sorted(dps)  # growing the batch never narrows the mesh
        for k, d in enumerate(dps):
            assert ladder.plan_for_batch(granule << k).dp_size == d


# ---------------------------------------------------------------------------
# reshard / place
# ---------------------------------------------------------------------------


class TestReshard:
    def _state(self):
        return init_state(small.mlp_init(jax.random.key(0), D), sgd(momentum=0.9))

    def test_same_rung_is_strict_noop(self):
        ladder = MeshLadder(granule=16)
        state = place(self._state(), ladder.rungs[1].plan)
        # an equal plan built separately still counts as the same rung
        clone = MeshLadder(granule=16).rungs[1].plan
        assert same_plan(ladder.rungs[1].plan, clone)
        assert reshard(state, ladder.rungs[1].plan, clone) is state

    def test_cross_rung_is_value_exact(self):
        ladder = MeshLadder(granule=16)
        state = self._state()
        host = [np.asarray(x) for x in jax.tree.leaves(state)]
        wide = place(state, ladder.full.plan)
        narrow = reshard(wide, ladder.full.plan, ladder.rungs[0].plan,
                         donate=False)
        for ref, leaf in zip(host, jax.tree.leaves(narrow)):
            np.testing.assert_array_equal(ref, np.asarray(leaf))
        mesh_dev = narrow.params["fc1"]["kernel"].sharding.mesh.devices
        assert mesh_dev.size == 1  # genuinely moved to the 1-wide rung

    def test_reshard_to_none_gathers_single_device(self):
        ladder = MeshLadder(granule=16)
        state = place(self._state(), ladder.full.plan)
        gathered = reshard(state, ladder.full.plan, None, donate=False)
        leaf = jax.tree.leaves(gathered)[0]
        assert len(leaf.devices()) == 1

    def test_different_rungs_are_not_same_plan(self):
        ladder = MeshLadder(granule=16)
        assert not same_plan(ladder.rungs[0].plan, ladder.rungs[1].plan)
        assert not same_plan(ladder.rungs[0].plan, None)
        assert same_plan(None, None)

    def test_place_without_plan_is_plain_arrays(self):
        state = place(self._state(), None)
        assert all(len(x.devices()) == 1 for x in jax.tree.leaves(state))


# ---------------------------------------------------------------------------
# the golden elastic trajectory (the tentpole acceptance test)
# ---------------------------------------------------------------------------


def _run(mode, epochs=5, prefetch=True):
    train, val, _ = sigmoid_synthetic(n=N, d=D, seed=SEED)
    ladder = MeshLadder(granule=16) if mode == "elastic" else None
    if mode == "full":
        ctx = use_plan(ShardingPlan(mesh=make_mesh((8,), ("data",))))
    else:
        ctx = contextlib.nullcontext()
    with ctx:
        t = Trainer(_fns(), small.mlp_init(jax.random.key(SEED), D),
                    sgd(momentum=0.9), _controller(), train, val,
                    estimator="exact", seed=SEED, elastic=ladder,
                    prefetch=prefetch)
        hist = t.run(epochs, verbose=False)
    return t, hist


def test_golden_elastic_trajectory_matches_full_mesh():
    """An elastic run crossing >= 2 rung transitions must produce the same
    schedule and numerically identical params as the identical DiveBatch run
    pinned to the full 8-device mesh, within f32 reduction-order tolerance
    (different dp widths sum microbatch gradients in different orders; the
    programs are arithmetically identical otherwise). The compile count must
    stay within the (bucket, rung) bound."""
    te, he = _run("elastic")
    tf, hf = _run("full")

    assert [h.batch_size for h in he] == [h.batch_size for h in hf]
    assert te.engine.stats.reshards >= 2  # >= 2 genuine rung transitions
    assert len(set(te.engine.stats.rungs)) >= 2
    for a, b in zip(jax.tree.leaves(te.state.params),
                    jax.tree.leaves(tf.state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose([h.val_loss for h in he],
                               [h.val_loss for h in hf], rtol=1e-4)

    # EngineStats-asserted (bucket, rung) bound
    stats = te.engine.stats
    ladder = MeshLadder(granule=16)
    bound = num_buckets(256, 16) * ladder.num_rungs
    assert stats.compiles <= bound
    assert stats.compiles == len(set(zip(stats.buckets, stats.rungs)))
    # rung is a function of the bucket here: one compile per bucket, so the
    # practical count is far below the worst case
    assert stats.compiles == len(set(stats.buckets))
    # every compile's rung is the ladder's choice for its bucket
    for bucket, rung in zip(stats.buckets, stats.rungs):
        assert rung == ladder.rung_for_batch(bucket).index


def test_elastic_rung_tokens_key_the_engine_cache():
    """Returning to an already-visited (bucket, rung) must be a cache hit;
    the same bucket on a different rung must not be."""
    train, _, _ = sigmoid_synthetic(n=512, d=16, seed=0)
    from repro.train import StepEngine

    fns = ModelFns(batch_loss=small.logreg_batch_loss,
                   example_loss=small.logreg_loss)
    ladder = MeshLadder(granule=16)
    eng = StepEngine.for_model_fns(fns, sgd(), estimator="moment",
                                   donate=False)
    state = init_state(small.logreg_init(jax.random.key(0), 16), sgd())

    def put(idx, rung):
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = NamedSharding(rung.plan.mesh, P(("data",)))
        return {k: jax.device_put(jnp.asarray(v), sh)
                for k, v in train.get(idx).items()}

    r1, r3 = ladder.rungs[1], ladder.rungs[3]
    batch = np.arange(64)
    state = place(state, r1.plan)
    eng.rung = r1.index
    state, _ = eng.step(state, put(batch, r1), 0.1)
    state, _ = eng.step(state, put(batch, r1), 0.1)
    assert eng.stats.compiles == 1 and eng.stats.bucket_hits == 1
    # same bucket (64), different rung: its own compile
    state = reshard(state, r1.plan, r3.plan, donate=False)
    eng.rung = r3.index
    state, _ = eng.step(state, put(batch, r3), 0.1)
    assert eng.stats.compiles == 2
    assert list(zip(eng.stats.buckets, eng.stats.rungs)) == [(64, 1), (64, 3)]
    # back to the first rung: hit, not compile
    state = reshard(state, r3.plan, r1.plan, donate=False)
    eng.rung = r1.index
    state, _ = eng.step(state, put(batch, r1), 0.1)
    assert eng.stats.compiles == 2 and eng.stats.bucket_hits == 2


def test_elastic_init_does_not_donate_caller_params():
    """The initial rung placement must not invalidate the arrays the caller
    handed in (init_state aliases them); only rung TRANSITIONS may donate."""
    train, val, _ = sigmoid_synthetic(n=256, d=16, seed=0)
    params = jax.tree.map(jnp.asarray, small.logreg_init(jax.random.key(0), 16))
    fns = ModelFns(batch_loss=small.logreg_batch_loss)
    Trainer(fns, params, sgd(), _controller(), train, val, estimator="none",
            elastic=MeshLadder(granule=16))
    assert not any(x.is_deleted() for x in jax.tree.leaves(params))
    float(fns.batch_loss(params, {k: jnp.asarray(v) for k, v in
                                  train.get(np.arange(16)).items()}))


def test_elastic_under_ambient_plan_raises():
    train, val, _ = sigmoid_synthetic(n=256, d=16, seed=0)
    fns = ModelFns(batch_loss=small.logreg_batch_loss)
    with use_plan(ShardingPlan(mesh=make_mesh((8,), ("data",)))):
        with pytest.raises(ValueError, match="ambig"):
            Trainer(fns, small.logreg_init(jax.random.key(0), 16), sgd(),
                    _controller(), train, val, estimator="none",
                    elastic=MeshLadder(granule=16))


# ---------------------------------------------------------------------------
# checkpoint round-trips across sharding plans
# ---------------------------------------------------------------------------


class TestCheckpointAcrossPlans:
    def _trainer(self, mgr, plan=None, elastic=None):
        train, val, _ = sigmoid_synthetic(n=N, d=D, seed=SEED)
        ctx = use_plan(plan) if plan is not None else contextlib.nullcontext()
        with ctx:
            return Trainer(_fns(), small.mlp_init(jax.random.key(SEED), D),
                           sgd(momentum=0.9), _controller(), train, val,
                           estimator="exact", seed=SEED, ckpt=mgr,
                           elastic=elastic)

    def _dp8(self):
        return ShardingPlan(mesh=make_mesh((8,), ("data",)))

    def test_save_unsharded_restore_dp8_and_reverse(self, tmp_path):
        """A checkpoint is topology-free: save under no plan -> restore under
        --dp 8 (and the reverse) with identical params and a correctly
        resumed cursor."""
        mgr = CheckpointManager(str(tmp_path / "a"), keep=2)
        t1 = self._trainer(mgr)
        t1.run(2, verbose=False)
        t1.save()
        ref = [np.asarray(x) for x in jax.tree.leaves(t1.state.params)]

        t2 = self._trainer(mgr, plan=self._dp8())
        assert t2.resume()
        assert t2.cursor.epoch == 2 and t2.cursor.batch_index == 0
        assert t2.controller.epoch == 2
        for a, b in zip(ref, jax.tree.leaves(t2.state.params)):
            np.testing.assert_array_equal(a, np.asarray(b))
        # restored onto the live 8-device plan, batches shard over it
        assert t2.state.params["fc1"]["kernel"].sharding.mesh.devices.size == 8

        # reverse: save under dp8, restore unsharded
        t2.run(1, verbose=False)
        t2.save()
        t3 = self._trainer(mgr)
        assert t3.resume()
        assert t3.cursor.epoch == 3
        for a, b in zip(jax.tree.leaves(t2.state.params),
                        jax.tree.leaves(t3.state.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert len(t3.state.params["fc1"]["kernel"].devices()) == 1

    def test_restore_with_plan_kwarg_places_trees(self, tmp_path):
        """CheckpointManager.restore(plan=...) reuses elastic.reshard.place:
        the restored trees land on the plan's inferred shardings."""
        mgr = CheckpointManager(str(tmp_path), keep=2)
        params = {"w": jnp.arange(16.0).reshape(2, 8), "b": jnp.ones(8)}
        mgr.save(1, {"params": params}, extra={"m": 64})
        plan = self._dp8()
        out, extra = mgr.restore({"params": params}, plan=plan)
        assert extra["m"] == 64
        np.testing.assert_array_equal(np.asarray(out["params"]["w"]),
                                      np.asarray(params["w"]))
        assert out["params"]["w"].sharding.mesh.devices.size == 8

    def test_elastic_resume_lands_on_checkpointed_rung(self, tmp_path):
        """Saved on one rung, resumed on another: a fresh elastic Trainer
        starts on the ladder's rung for ITS m0, then resume() re-derives the
        rung from the restored controller state (supervisor restart path)."""
        mgr = CheckpointManager(str(tmp_path), keep=3)
        ladder = MeshLadder(granule=16)
        t1 = self._trainer(mgr, elastic=ladder)
        start_rung = t1.rung.index
        t1.run(2, verbose=False)  # diversity growth moves m well past m0
        t1.save()
        # the rung the NEXT epoch will run on: derived from the restored
        # controller's batch size, not from whatever rung the saver was on
        next_rung = ladder.rung_for_batch(t1.controller.batch_size).index

        t2 = self._trainer(mgr, elastic=MeshLadder(granule=16))
        assert t2.resume()
        assert t2.rung.index == next_rung != start_rung
        for a, b in zip(jax.tree.leaves(t1.state.params),
                        jax.tree.leaves(t2.state.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # and the resumed trajectory continues exactly like an uncrashed one
        t3 = self._trainer(CheckpointManager(str(tmp_path / "c")),
                           elastic=MeshLadder(granule=16))
        t3.run(4, verbose=False)
        t2.run(2, verbose=False)
        np.testing.assert_allclose(
            [h.val_loss for h in t3.history[2:]],
            [h.val_loss for h in t2.history[2:]], rtol=1e-4)


# ---------------------------------------------------------------------------
# prefetch (satellite): trajectory bit-identical with and without
# ---------------------------------------------------------------------------


def test_prefetch_trajectory_bit_identical():
    t_pre, h_pre = _run("plain", epochs=3, prefetch=True)
    t_sync, h_sync = _run("plain", epochs=3, prefetch=False)
    assert [h.batch_size for h in h_pre] == [h.batch_size for h in h_sync]
    assert [h.train_loss for h in h_pre] == [h.train_loss for h in h_sync]
    for a, b in zip(jax.tree.leaves(t_pre.state.params),
                    jax.tree.leaves(t_sync.state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_prefetch_iterator_order_and_depth():
    from repro.data import prefetch

    puts = []
    out = list(prefetch(range(5), put=lambda b: (puts.append(b), b)[1], depth=2))
    assert out == [0, 1, 2, 3, 4]
    assert puts == [0, 1, 2, 3, 4]
    with pytest.raises(ValueError, match="depth"):
        list(prefetch([1], put=lambda b: b, depth=0))


def test_prefetch_stays_ahead_of_consumer():
    """With depth=2 the put of batch b+1 is issued before batch b is
    consumed (that is the double buffer)."""
    from repro.data import prefetch

    events = []
    gen = prefetch(range(3), put=lambda b: (events.append(("put", b)), b)[1])
    first = next(gen)
    events.append(("consume", first))
    second = next(gen)
    events.append(("consume", second))
    assert events[:3] == [("put", 0), ("put", 1), ("consume", 0)]
