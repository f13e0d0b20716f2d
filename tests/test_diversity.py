"""Property + unit tests for the gradient-diversity estimators (paper §2.2/§4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core import diversity


def _accumulate_all(g: np.ndarray, micro: int, exact: bool):
    params = {"w": jnp.zeros(g.shape[1])}
    st_ = diversity.init_state(params)
    for i in range(0, len(g), micro):
        mb = g[i : i + micro]
        psn = jnp.asarray(np.sum(mb**2)) if exact else None
        st_ = diversity.accumulate(st_, {"w": jnp.asarray(mb.mean(0))}, len(mb), psn)
    return st_


def _true_delta(g: np.ndarray) -> float:
    return float(np.sum(np.sum(g**2, -1)) / np.sum(np.sum(g, 0) ** 2))


class TestExactEstimator:
    def test_matches_definition(self):
        g = np.random.default_rng(0).normal(0.3, 1.0, (64, 16)).astype(np.float32)
        st_ = _accumulate_all(g, 8, exact=True)
        assert np.isclose(float(diversity.diversity_exact(st_)), _true_delta(g), rtol=1e-5)

    @given(
        n=st.sampled_from([8, 32, 64]),
        d=st.sampled_from([3, 17]),
        seed=st.integers(0, 2**16),
    )
    @settings(max_examples=20, deadline=None)
    def test_bounds(self, n, d, seed):
        """Cauchy-Schwarz: n * Delta >= 1 always; equality iff all equal."""
        g = np.random.default_rng(seed).normal(0.5, 1.0, (n, d)).astype(np.float64)
        delta = _true_delta(g)
        assert n * delta >= 1.0 - 1e-9

    @given(c=st.floats(0.1, 10.0), seed=st.integers(0, 2**16))
    @settings(max_examples=20, deadline=None)
    def test_scale_invariance(self, c, seed):
        g = np.random.default_rng(seed).normal(0.2, 1.0, (32, 8)).astype(np.float64)
        assert np.isclose(_true_delta(g), _true_delta(c * g), rtol=1e-9)

    def test_identical_gradients(self):
        """All-equal gradients -> Delta = 1/n (no diversity)."""
        g = np.tile(np.ones((1, 5), np.float32), (20, 1))
        assert np.isclose(_true_delta(g), 1 / 20)

    def test_orthogonal_gradients(self):
        """Orthogonal gradients -> Delta = 1 (max diversity, m can be ~n)."""
        g = np.eye(16, dtype=np.float32)
        assert np.isclose(_true_delta(g), 1.0)


class TestMomentEstimator:
    def test_unbiased_on_gaussian(self):
        rng = np.random.default_rng(1)
        ratios = []
        for _ in range(40):
            g = rng.normal(0.3, 1.0, (512, 12)).astype(np.float32)
            st_ = _accumulate_all(g, 32, exact=False)
            ratios.append(float(diversity.diversity_moment(st_)) / _true_delta(g))
        assert abs(np.mean(ratios) - 1.0) < 0.05, np.mean(ratios)

    def test_single_microbatch_degenerate(self):
        g = np.random.default_rng(2).normal(size=(32, 8)).astype(np.float32)
        st_ = _accumulate_all(g, 32, exact=False)  # one microbatch == epoch
        val = float(diversity.diversity_moment(st_))
        assert np.isfinite(val) and val > 0

    def test_moment_vs_exact_tracks(self):
        """Across parameter scales the two tiers must order the same way."""
        rng = np.random.default_rng(3)
        exact, moment = [], []
        for mean in (0.05, 0.3, 1.0):
            g = rng.normal(mean, 1.0, (256, 10)).astype(np.float32)
            st_e = _accumulate_all(g, 16, exact=True)
            st_m = _accumulate_all(g, 16, exact=False)
            exact.append(float(diversity.diversity_exact(st_e)))
            moment.append(float(diversity.diversity_moment(st_m)))
        assert np.argsort(exact).tolist() == np.argsort(moment).tolist()


class TestPersampleHelpers:
    def test_vmap_grads_match_manual(self):
        def loss(params, ex):
            return jnp.sum((params["w"] * ex["x"] - ex["y"]) ** 2)

        params = {"w": jnp.asarray([1.0, 2.0])}
        batch = {"x": jnp.asarray([[1.0, 0.0], [0.0, 1.0]]),
                 "y": jnp.asarray([[0.0, 0.0], [0.0, 0.0]])}
        sq = diversity.persample_sq_norms(loss, params, batch)
        # grads: sample0 d/dw = [2*1*1, 0] -> norm^2 4; sample1 [0, 2*2] -> 16
        np.testing.assert_allclose(np.asarray(sq), [4.0, 16.0], rtol=1e-6)

    def test_oracle_dataset_diversity(self):
        def loss(params, ex):
            return jnp.mean((params["w"] @ ex["x"] - ex["y"]) ** 2)

        rng = np.random.default_rng(4)
        x = rng.normal(size=(50, 6)).astype(np.float32)
        y = rng.normal(size=(50,)).astype(np.float32)
        params = {"w": jnp.asarray(rng.normal(size=6).astype(np.float32))}
        batches = [
            {"x": jnp.asarray(x[i : i + 10]), "y": jnp.asarray(y[i : i + 10])}
            for i in range(0, 50, 10)
        ]
        val = diversity.dataset_diversity(loss, params, batches)
        grads = np.asarray(
            jax.vmap(jax.grad(loss), in_axes=(None, 0))(
                params, {"x": jnp.asarray(x), "y": jnp.asarray(y)}
            )["w"]
        )
        assert np.isclose(float(val), _true_delta(grads), rtol=1e-4)


class TestResetAndState:
    def test_reset(self):
        params = {"w": jnp.ones(3)}
        st_ = diversity.init_state(params)
        st_ = diversity.accumulate(st_, {"w": jnp.ones(3)}, 4, None)
        st_ = diversity.reset_state(st_)
        assert float(st_.sq_norm_sum) == 0.0
        assert float(st_.sample_count) == 0.0
        assert np.all(np.asarray(st_.grad_sum["w"]) == 0)

    def test_reset_keeps_each_leaf_sharding(self):
        """A reset under a sharded state leaves every accumulator where it
        was: the next step's executable is compiled for that layout."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.dist.plan import make_mesh

        mesh = make_mesh((8,), ("data",))
        st_ = diversity.accumulate(diversity.init_state({"w": jnp.ones(16)}),
                                   {"w": jnp.ones(16)}, 4, None)
        st_ = st_._replace(grad_sum={"w": jax.device_put(
            st_.grad_sum["w"], NamedSharding(mesh, P("data")))})
        reset = diversity.reset_state(st_)
        for before, after in zip(jax.tree.leaves(st_), jax.tree.leaves(reset)):
            assert after.sharding == before.sharding
        assert np.all(np.asarray(reset.grad_sum["w"]) == 0)
