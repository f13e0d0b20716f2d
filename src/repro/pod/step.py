"""The cross-pod train step: shard_map over a (pods, data) mesh with the
gradient mean routed through the error-feedback int8 compressor.

Structure per step (one shard_map program over the rung's full mesh):

  1. local value_and_grad on each device's batch shard;
  2. ``pmean`` over the within-pod ``data`` axis — the fast ICI reduction,
     exact f32;
  3. the pod-level means cross the ``pod`` axis through
     ``dist.compression.compressed_pod_mean`` — int8 payload + f32 scale on
     the wire (the only DCN bytes), residuals carried shard-local in
     ``TrainState.err_state``;
  4. replicated optimizer update (cross-pod plans keep ``fsdp=()`` so params
     are replicated — the update is computed identically everywhere).

Diversity accumulates inside the same program, exactly like the plain step:
the ``moment`` tier treats each POD's uncompressed mean as one microbatch
(``mb_count += pods``, so the decode's small-batch size is the per-pod
batch); the ``exact`` tier psums the per-sample squared norms over both
axes.  The ``gram`` tier's probe kernels are not wired across pods yet.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.core import diversity
from repro.dist.compression import compressed_pod_mean
from repro.optim import Optimizer, apply_updates
from repro.train.state import TrainState
from repro.utils import pytree as ptu

PyTree = Any


def make_pod_train_step(
    rung,
    optimizer: Optimizer,
    *,
    loss_fn: Callable,
    example_loss: Callable | None = None,
    diversity_on: bool = True,
    estimator: str = "moment",
    compress: bool = True,
    pod_axis: str = "pod",
    data_axis: str = "data",
) -> Callable[[TrainState, dict, jax.Array], tuple[TrainState, dict]]:
    """Returns ``train_step(state, batch, lr) -> (state, metrics)`` for a
    cross-pod ``Rung`` (its mesh must carry ``(pod_axis, data_axis)``).

    ``loss_fn(params, batch) -> scalar`` is the mean loss over a batch
    shard; ``example_loss`` is required for the exact tier.  With
    ``compress=True`` (the production setting) ``state.err_state`` must hold
    the stacked per-pod residual tree (``PodLadder.adapt_state`` installs
    it); ``compress=False`` runs the same program with an exact f32 pmean
    across pods — the baseline the compression golden test compares against.
    """
    mesh = rung.plan.mesh
    pods = int(mesh.shape[pod_axis])
    dpp = int(mesh.shape[data_axis])
    if pods < 2:
        raise ValueError(f"cross-pod step needs a pods>=2 mesh axis, got {pods}")
    if estimator == "gram":
        raise NotImplementedError(
            "the gram tier's probe kernels are not wired across pods; use "
            "'moment' (production) or 'exact' (reference) on cross-pod rungs"
        )
    if estimator not in ("exact", "moment"):
        raise ValueError(f"unknown cross-pod estimator {estimator!r}")
    if estimator == "exact" and example_loss is None:
        raise ValueError("estimator='exact' needs example_loss")

    def body(state: TrainState, batch: dict, lr: jax.Array):
        params = state.params  # replicated: cross-pod plans keep fsdp=()
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        # within-pod reduction (ICI): exact f32 mean over the pod's shards
        grads = jax.lax.pmean(grads, data_axis)
        local_b = jax.tree.leaves(batch)[0].shape[0]
        global_b = local_b * pods * dpp

        if compress:
            if state.err_state is None:
                raise ValueError(
                    "compress=True needs TrainState.err_state (the stacked "
                    "per-pod residuals PodLadder.adapt_state installs)"
                )
            err = jax.tree.map(lambda e: e[0], state.err_state)
            mean, new_err = compressed_pod_mean(grads, err, pod_axis)
            new_err = jax.tree.map(lambda e: e[None], new_err)
        else:
            mean = jax.lax.pmean(grads, pod_axis)
            new_err = state.err_state

        div_state = state.div_state
        if diversity_on:
            b = jnp.float32(global_b)
            if estimator == "exact":
                sq = jax.lax.psum(
                    jnp.sum(diversity.persample_sq_norms(example_loss, params, batch)),
                    (pod_axis, data_axis),
                )
                mb = jnp.float32(1.0)  # decode expects m=1 small batches
            else:
                # one "microbatch" per pod: the UNCOMPRESSED pod mean is the
                # small-batch statistic, so quantization noise never enters Q
                m_pod = jnp.float32(global_b // pods)
                sq = jax.lax.psum((m_pod * m_pod) * ptu.tree_sq_norm(grads), pod_axis)
                mb = jnp.float32(pods)
            div_state = diversity.DiversityState(
                grad_sum=jax.tree.map(
                    lambda acc, g: acc + b.astype(acc.dtype) * g.astype(acc.dtype),
                    div_state.grad_sum,
                    mean,
                ),
                sq_norm_sum=div_state.sq_norm_sum + sq,
                mb_count=div_state.mb_count + mb,
                sample_count=div_state.sample_count + b,
            )

        updates, opt_state = optimizer.update(mean, state.opt_state, params, lr)
        params = apply_updates(params, updates)
        new_state = TrainState(
            params=params,
            opt_state=opt_state,
            div_state=div_state,
            step=state.step + 1,
            err_state=new_err,
        )
        metrics = {
            "loss": jax.lax.pmean(loss, (pod_axis, data_axis)),
            "grad_norm_sq": ptu.tree_sq_norm(mean),
        }
        return new_state, metrics

    # Specs are pytree prefixes: one P per TrainState field covers its whole
    # subtree.  Everything is replicated except the batch (sharded over both
    # axes) and the error residuals (stacked (pods, ...) leaves, one shard
    # per pod).
    state_spec = TrainState(
        params=P(), opt_state=P(), div_state=P(), step=P(),
        err_state=P(pod_axis),
    )
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(state_spec, P((pod_axis, data_axis)), P()),
        out_specs=(state_spec, P()),
        check_vma=False,
    )
