"""repro.dist.plan: use_plan/current_plan nesting + re-entrancy, and the
constrain() no-op contract (exact identity, nothing added to the jaxpr) when
no plan is active."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, AxisType, PartitionSpec as P

from repro.dist.plan import (
    ShardingPlan,
    constrain,
    current_act_specs,
    current_plan,
    make_mesh,
    shard_local,
    use_plan,
)


def _plan(tag="data"):
    mesh = AbstractMesh((2, 4), ("pod", tag))
    return ShardingPlan(mesh=mesh, dp=("pod", tag), fsdp=(tag,), tp=tag,
                        ep=(tag,))


class TestPlanContext:
    def test_no_plan_by_default(self):
        assert current_plan() is None
        assert current_act_specs() == {}

    def test_use_plan_sets_and_restores(self):
        plan = _plan()
        with use_plan(plan, {"residual": P(None)}) as active:
            assert active is plan
            assert current_plan() is plan
            assert current_act_specs() == {"residual": P(None)}
        assert current_plan() is None

    def test_nesting_restores_outer(self):
        outer, inner = _plan(), _plan("model")
        with use_plan(outer):
            with use_plan(inner):
                assert current_plan() is inner
            assert current_plan() is outer
        assert current_plan() is None

    def test_reentrant_same_plan(self):
        plan = _plan()
        with use_plan(plan):
            with use_plan(plan):
                assert current_plan() is plan
            assert current_plan() is plan

    def test_restored_after_exception(self):
        plan = _plan()
        with pytest.raises(RuntimeError):
            with use_plan(plan):
                raise RuntimeError("boom")
        assert current_plan() is None

    def test_axis_sizes(self):
        plan = _plan()
        assert plan.dp_size == 8
        assert plan.tp_size == 4
        assert plan.axis_size(None) == 1


class TestConstrainNoOp:
    def test_identity_without_plan(self):
        x = jnp.arange(8.0)
        assert constrain(x, "residual") is x

    def test_identity_for_unknown_name(self):
        x = jnp.arange(8.0)
        with use_plan(_plan(), {"residual": P(None, None)}):
            assert constrain(x, "not_registered") is x

    def test_identity_for_rank_mismatch(self):
        x = jnp.arange(8.0)  # 1-D vs a 3-D spec: nothing to say, exact no-op
        with use_plan(_plan(), {"residual": P(("pod", "data"), None, "data")}):
            assert constrain(x, "residual") is x

    def test_identity_for_indivisible_dims(self):
        x = jnp.zeros((7, 5))  # neither dim divides the 2x4 mesh axes
        with use_plan(_plan(), {"residual": P(("pod", "data"), "data")}):
            assert constrain(x, "residual") is x

    def test_no_trace_residue_without_plan(self):
        jaxpr = jax.make_jaxpr(lambda x: constrain(x, "residual"))(jnp.ones((4,)))
        assert jaxpr.eqns == []  # identity: no tracer leaks, no inserted ops

    def test_constraint_applies_on_real_mesh(self):
        # conftest forces 8 host devices, so a real (8,)-mesh exists here
        mesh = make_mesh((8,), ("data",))
        plan = ShardingPlan(mesh=mesh, dp=("data",), fsdp=("data",),
                            tp="data", ep=("data",))
        with use_plan(plan, {"residual": P("data")}):
            out = jax.jit(lambda x: constrain(x, "residual"))(jnp.arange(16.0))
        assert len(out.sharding.device_set) == 8
        # indivisible input under the same plan degrades to a working no-op
        with use_plan(plan, {"residual": P("data")}):
            out = jax.jit(lambda x: constrain(x, "residual"))(jnp.arange(7.0))
        assert out.shape == (7,)


def test_make_mesh_has_auto_axes():
    mesh = make_mesh((2, 4), ("pod", "data"))
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)
    assert mesh.devices.size == 8


class TestShardLocal:
    def _plan(self):
        mesh = make_mesh((8,), ("data",))
        return ShardingPlan(mesh=mesh, dp=("data",), fsdp=("data",), tp=None)

    def test_plain_call_without_plan(self):
        x = jnp.arange(16.0).reshape(8, 2)
        jaxpr = jax.make_jaxpr(
            lambda x: shard_local(lambda a: a * 2, (x,), (("dp", None),), ("dp", None))
        )(x)
        assert "shard_map" not in str(jaxpr)

    @pytest.mark.parametrize("rows", [16, 6], ids=["divisible", "indivisible"])
    def test_matches_the_plain_call_per_shard(self, rows):
        """A row-local function gives the plain call's result whether the batch
        splits over the 8 devices or (6 rows) has to stay whole on each."""
        x = jnp.arange(rows * 3.0).reshape(rows, 3)

        def row_norm(a):  # needs only its own rows
            return a / jnp.sum(a, axis=1, keepdims=True)

        with use_plan(self._plan()):
            jaxpr = jax.make_jaxpr(lambda a: shard_local(
                row_norm, (a,), (("dp", None),), ("dp", None)))(x)
            got = jax.jit(lambda a: shard_local(
                row_norm, (a,), (("dp", None),), ("dp", None)))(x)
        assert "shard_map" in str(jaxpr)
        np.testing.assert_allclose(np.asarray(got), np.asarray(row_norm(x)),
                                   rtol=1e-6)
