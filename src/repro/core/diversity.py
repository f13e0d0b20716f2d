"""Gradient-diversity estimation (the paper's core quantity).

Gradient diversity (Yin et al. 2018, Definition 1):

    Delta_S(theta) = sum_i ||g_i||^2 / || sum_i g_i ||^2

DiveBatch (Algorithm 1) accumulates, across all microbatches of an epoch,
  * the running sum of gradients                      -> ``grad_sum`` (pytree)
  * the running sum of per-sample grad sq-norms       -> ``sq_norm_sum``
and at the epoch boundary sets  m_{k+1} = min(m_max, delta * n * Delta_hat).

Three estimator tiers provide the numerator at different scales:

  exact   vmap(grad) per sample. Reference semantics; O(B) memory blowup.
  gram    probe trick + per-sample-gradient-norm identity on dense layers
          (see kernels/psgn.py); exact for matmul parameters, which dominate.
  moment  recovers sum_i ||g_i||^2 unbiasedly from *microbatch-sum* gradient
          norms using E||sum_{i<=m} g_i||^2 = m E||g||^2 + m(m-1) ||mu||^2.
          Zero extra backward work -> the tier used at 7B..1T scale.

All accumulation state is a pytree (``DiversityState``) so it shards, jits,
checkpoints, and donates like any other training state.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.utils import pytree as ptu

PyTree = Any

EPS = 1e-20


class DiversityState(NamedTuple):
    """Within-epoch accumulators. Reset at every epoch boundary.

    grad_sum      running sum over all per-sample gradients seen this epoch.
                  (Each microbatch contributes ``microbatch_size * mean_grad``.)
    sq_norm_sum   exact/gram: running sum_i ||g_i||^2.
                  moment:     running sum_j ||microbatch_sum_grad_j||^2.
    mb_count      number of microbatches accumulated (moment estimator).
    sample_count  number of samples accumulated.
    """

    grad_sum: PyTree
    sq_norm_sum: jax.Array
    mb_count: jax.Array
    sample_count: jax.Array


def init_state(params: PyTree, accum_dtype=jnp.float32) -> DiversityState:
    return DiversityState(
        grad_sum=ptu.tree_zeros_like(params, dtype=accum_dtype),
        sq_norm_sum=jnp.zeros((), jnp.float32),
        mb_count=jnp.zeros((), jnp.float32),
        sample_count=jnp.zeros((), jnp.float32),
    )


def reset_state(state: DiversityState) -> DiversityState:
    """Zeroed accumulators.  Called eagerly, each leaf keeps the sharding of
    the one it replaces (``zeros_like`` of a concrete array does; under jit
    the compiler would be free to put the zeros anywhere)."""
    return jax.tree.map(jnp.zeros_like, state)


# ---------------------------------------------------------------------------
# Per-microbatch accumulation (jit-side, called inside train_step)
# ---------------------------------------------------------------------------


def accumulate(
    state: DiversityState,
    mean_grad: PyTree,
    microbatch_size: jax.Array | int,
    persample_sq_norm_sum: jax.Array | None = None,
) -> DiversityState:
    """Fold one microbatch's gradient statistics into the state.

    mean_grad              the (possibly all-reduced) mean gradient of the
                           microbatch — the same tensor the optimizer consumes,
                           so this costs one extra axpy over the param tree.
    microbatch_size        number of samples in the microbatch (global).
    persample_sq_norm_sum  sum_i ||g_i||^2 over the microbatch, if an exact or
                           gram estimator computed it. If None, the moment
                           estimator's statistic ||m * mean_grad||^2 is used.
    """
    m = jnp.asarray(microbatch_size, jnp.float32)
    grad_sum = jax.tree.map(
        lambda acc, g: acc + m.astype(acc.dtype) * g.astype(acc.dtype),
        state.grad_sum,
        mean_grad,
    )
    if persample_sq_norm_sum is None:
        contrib = (m * m) * ptu.tree_sq_norm(mean_grad)  # ||sum over microbatch||^2
    else:
        contrib = jnp.asarray(persample_sq_norm_sum, jnp.float32)
    return DiversityState(
        grad_sum=grad_sum,
        sq_norm_sum=state.sq_norm_sum + contrib,
        mb_count=state.mb_count + 1.0,
        sample_count=state.sample_count + m,
    )


# ---------------------------------------------------------------------------
# Epoch-boundary estimates (jit-friendly scalar math)
# ---------------------------------------------------------------------------


def diversity_exact(state: DiversityState) -> jax.Array:
    """Delta_hat for the exact/gram tiers: sq_norm_sum / ||grad_sum||^2."""
    denom = ptu.tree_sq_norm(state.grad_sum)
    return state.sq_norm_sum / jnp.maximum(denom, EPS)


def diversity_moment(state: DiversityState) -> jax.Array:
    """Delta_hat from microbatch-sum norms (no per-sample work).

    With J microbatches of (average) size m, n = J*m samples:
        Q := sum_j ||S_j||^2,  E[Q] = J*m*E2 + J*m*(m-1)*M
        R := ||sum_i g_i||^2,  E[R] = n*E2 + n*(n-1)*M
    where E2 = E||g||^2 and M = ||mu||^2. Solving:
        M  = (R - Q) / (n*(n - m))        (clamped at >= 0)
        E2 = Q/n - (m - 1)*M              (clamped at >= eps)
    and Delta_hat = n*E2 / R.
    """
    n = jnp.maximum(state.sample_count, 1.0)
    J = jnp.maximum(state.mb_count, 1.0)
    m = n / J
    Q = state.sq_norm_sum
    R = ptu.tree_sq_norm(state.grad_sum)
    denom = jnp.maximum(n * (n - m), EPS)
    M = jnp.maximum((R - Q) / denom, 0.0)
    E2 = jnp.maximum(Q / n - (m - 1.0) * M, EPS)
    # Single-microbatch epoch degenerates (n == m): fall back to treating the
    # microbatch statistic as exact — Delta_hat = Q/R then equals 1 scaled.
    delta = jnp.where(n - m < 0.5, Q / jnp.maximum(R, EPS), n * E2 / jnp.maximum(R, EPS))
    return delta


def estimate(state: DiversityState, estimator: str) -> jax.Array:
    if estimator in ("exact", "gram"):
        return diversity_exact(state)
    if estimator == "moment":
        return diversity_moment(state)
    raise ValueError(f"unknown estimator {estimator!r}")


# ---------------------------------------------------------------------------
# Per-sample gradient helpers (exact tier + Oracle)
# ---------------------------------------------------------------------------


def persample_grads(
    loss_fn: Callable[[PyTree, Any], jax.Array], params: PyTree, batch: Any
) -> PyTree:
    """vmap(grad): per-sample gradients. loss_fn(params, example) -> scalar."""
    return jax.vmap(jax.grad(loss_fn), in_axes=(None, 0))(params, batch)


def persample_sq_norms(
    loss_fn: Callable[[PyTree, Any], jax.Array], params: PyTree, batch: Any
) -> jax.Array:
    """(B,) array of per-sample gradient squared norms (exact tier)."""
    grads = persample_grads(loss_fn, params, batch)
    leaves = jax.tree.leaves(
        jax.tree.map(
            lambda g: jnp.sum(
                jnp.square(g.astype(jnp.float32)).reshape(g.shape[0], -1), axis=-1
            ),
            grads,
        )
    )
    return functools.reduce(jnp.add, leaves)


def dataset_diversity(
    loss_fn: Callable[[PyTree, Any], jax.Array],
    params: PyTree,
    batches,
) -> jax.Array:
    """ORACLE: exact Delta_S(theta) over an iterable of batches (one pass).

    ``batches`` yields pytrees whose leaves have a leading sample axis. All
    gradients are evaluated at the *same* fixed params (unlike DiveBatch's
    within-epoch accumulation) — this is the paper's Oracle baseline.
    """
    sq_fn = jax.jit(lambda p, b: persample_sq_norms(loss_fn, p, b))

    def sum_fn(p, b):
        bsz = jax.tree.leaves(b)[0].shape[0]
        return ptu.tree_scale(
            jax.grad(lambda pp: jnp.mean(jax.vmap(lambda e: loss_fn(pp, e))(b)))(p), bsz
        )

    sum_fn = jax.jit(sum_fn)

    total_sq = jnp.zeros((), jnp.float32)
    grad_sum = None
    for batch in batches:
        total_sq = total_sq + jnp.sum(sq_fn(params, batch))
        gs = sum_fn(params, batch)
        grad_sum = gs if grad_sum is None else ptu.tree_add(grad_sum, gs)
    if grad_sum is None:
        raise ValueError("dataset_diversity: empty dataset")
    return total_sq / jnp.maximum(ptu.tree_sq_norm(grad_sum), EPS)
