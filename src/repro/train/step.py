"""The train step: microbatch gradient accumulation + DiveBatch diversity
accumulation, as one jitted program. Every training path (the host ``Trainer``,
``launch/train.py``, the multi-pod dry-run, ``examples/train_lm.py``) obtains
its compiled steps from here via ``train/engine.py::StepEngine``.

Batch-size adaptivity at scale = adapting ``num_micro`` (the accumulation
length): the microbatch shape is fixed per mesh, the global batch is
``num_micro * micro_batch``, and the compile cache is keyed by the power-of-2
``num_micro`` bucket (core/batch_policy.bucket).

All three diversity-estimator tiers run INSIDE the jitted step (``estimator``):

  moment  Q += ||microbatch_sum_grad||^2 per microbatch — zero extra backward
          work, the tier used at 7B..1T scale.
  exact   Q += sum_i ||g_i||^2 via vmap(grad(example_loss)) over each
          microbatch — reference semantics, O(m) memory blowup.
  gram    Q += probe-trick per-sample norms (kernels/psgn) from one extra
          probe-gradient pass — exact for the dense kernels that dominate.

so an epoch performs no per-step host transfer beyond the scalar metrics.

The microbatch re-layout ``(B, ...) -> (G, M, ...)`` is sharding-preserving:
it splits the dp-sharded batch dim as (dp, G, M/dp), transposes, and merges
(dp, M/dp) back into the microbatch dim — every microbatch stays evenly
spread over all dp shards with zero communication.

Diversity accumulation uses the moment estimator (DESIGN.md §3): per
microbatch it costs one tree-axpy into the (ZeRO-sharded) grad_sum
accumulator plus one squared-norm reduction of the mean gradient the
optimizer already has — no per-sample work.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import diversity
from repro.kernels import ops as kernel_ops
from repro.models import transformer as tf
from repro.optim import Optimizer, apply_updates
from repro.train.state import TrainState
from repro.utils import pytree as ptu

PyTree = Any


def _to_micro(x: jax.Array, num_micro: int, dp_size: int) -> jax.Array:
    b = x.shape[0]
    if b % num_micro != 0:
        raise ValueError(
            f"global batch {b} is not divisible by the num_micro bucket "
            f"{num_micro}; batch sizes must land on the bucket lattice "
            f"(core/batch_policy.bucket)"
        )
    m = b // num_micro
    if dp_size > 1 and m % dp_size == 0:
        x = x.reshape(dp_size, num_micro, m // dp_size, *x.shape[1:])
        x = jnp.moveaxis(x, 0, 1)
        return x.reshape(num_micro, m, *x.shape[3:])
    return x.reshape(num_micro, m, *x.shape[1:])


def make_train_step(
    cfg: ModelConfig | None,
    optimizer: Optimizer,
    num_micro: int,
    *,
    dp_size: int = 1,
    moe_groups: int = 1,
    diversity_on: bool = True,
    grad_accum_dtype=jnp.float32,
    loss_fn: Callable | None = None,
    has_aux: bool | None = None,
    estimator: str = "moment",
    example_loss: Callable | None = None,
    probe_loss: Callable | None = None,
    probe_specs: Callable | None = None,
    psn_chunk: int | None = None,
    psn_impl: str = "auto",
    psn_interpret: bool | None = None,
) -> Callable[[TrainState, dict, jax.Array], tuple[TrainState, dict]]:
    """Returns train_step(state, batch, lr) -> (state, metrics).

    ``loss_fn(params, batch)`` defaults to the transformer LM loss (``cfg``
    required then). ``has_aux`` says whether it returns ``(loss, aux)``;
    defaults to True for the LM loss, False for a custom scalar loss.

    ``estimator`` selects the in-jit diversity tier (see module docstring):
    "moment" needs nothing extra, "exact" needs ``example_loss(params,
    example)``, "gram" needs ``probe_loss(params, probes, batch) -> (loss,
    acts)`` plus ``probe_specs(params, batch_size)``.

    ``psn_chunk`` bounds the exact tier's vmap width: per-sample gradients
    are materialised ``psn_chunk`` samples at a time (peak extra memory
    ``psn_chunk x param-size`` instead of ``microbatch x param-size``).

    ``psn_impl`` picks how the EXACT tier computes per-sample norms:
    "vmap" is vmap(grad(example_loss)) — reference semantics for any model;
    "kernel" replaces it with one probe-gradient pass through the fused
    kernels/psgn lane (``||X^T D||^2`` plus the bias terms ``||sum_s d||^2``
    per probed layer) — no per-sample gradient trees at all, exact for
    bias-complete dense models, requires ``probe_loss``/``probe_specs``.
    "auto" keeps vmap whenever ``example_loss`` is provided (bit-stable
    default) and falls back to the kernel path when only probes exist.
    ``psn_interpret`` forces the Pallas interpret flag (None = on-TPU
    detection via repro.kernels.default_interpret).
    """
    counters: tuple[str, ...] = ()
    if loss_fn is None:
        if cfg is None:
            raise ValueError("make_train_step needs cfg or loss_fn")
        base_loss = lambda p, b: tf.loss_fn(cfg, p, b, moe_groups=moe_groups)
        aux = True
        counters = tf.step_counters(cfg)
    else:
        aux = has_aux if has_aux is not None else False
        base_loss = loss_fn if aux else (lambda p, b: (loss_fn(p, b), {}))
    if psn_impl not in ("auto", "vmap", "kernel"):
        raise ValueError(f"unknown psn_impl {psn_impl!r}")
    if psn_impl == "auto":
        psn_impl = "vmap" if example_loss is not None else "kernel"
    if diversity_on:
        if estimator == "exact":
            if psn_impl == "vmap" and example_loss is None:
                raise ValueError("estimator='exact' needs example_loss")
            if psn_impl == "kernel" and (probe_loss is None or probe_specs is None):
                raise ValueError(
                    "estimator='exact' with psn_impl='kernel' needs "
                    "probe_loss and probe_specs"
                )
        if estimator == "gram" and (probe_loss is None or probe_specs is None):
            raise ValueError("estimator='gram' needs probe_loss and probe_specs")
        if estimator not in ("exact", "gram", "moment"):
            raise ValueError(f"unknown in-step estimator {estimator!r}")

    def _probe_sq_norms(params, mb, *, bias):
        """One probe-gradient pass -> summed per-sample sq-norms via the
        Pallas psgn lane (same-shape layers fused into one launch)."""
        bsz = jax.tree.leaves(mb)[0].shape[0]
        probes = probe_specs(params, bsz)
        (_, acts), pgrads = jax.value_and_grad(
            probe_loss, argnums=1, has_aux=True
        )(params, probes, mb)
        return jnp.sum(
            kernel_ops.persample_sq_norm_tree(
                acts, pgrads, scale=float(bsz), bias=bias,
                interpret=psn_interpret,
            )
        )

    def _micro_sq_contrib(params, mb, mean_grads, micro_global):
        """This microbatch's contribution to DiversityState.sq_norm_sum."""
        if estimator == "exact":
            if psn_impl == "kernel":
                # the fused lane: no vmap, no per-sample gradient trees —
                # bias terms included so dense+bias models stay exact
                return _probe_sq_norms(params, mb, bias=True)
            # Chunked so the vmap'd per-sample gradient trees never exceed
            # psn_chunk x param-size of live memory (the loop unrolls at
            # trace time; chunk sums accumulate in order).
            n = jax.tree.leaves(mb)[0].shape[0]
            chunk = min(psn_chunk or n, n)
            total = jnp.zeros((), jnp.float32)
            for i in range(0, n, chunk):
                sub = jax.tree.map(lambda x: x[i : i + chunk], mb)
                total = total + jnp.sum(
                    diversity.persample_sq_norms(example_loss, params, sub)
                )
            return total
        if estimator == "gram":
            return _probe_sq_norms(params, mb, bias=False)
        m = jnp.float32(micro_global)
        return (m * m) * ptu.tree_sq_norm(mean_grads)

    def train_step(state: TrainState, batch: dict, lr: jax.Array):
        micro = jax.tree.map(lambda x: _to_micro(x, num_micro, dp_size), batch)
        global_batch = next(iter(jax.tree.leaves(batch))).shape[0]
        micro_global = global_batch // num_micro

        grad_fn = jax.value_and_grad(base_loss, has_aux=True)

        # The microbatch scan carries ONLY (grads_acc, scalars): the diversity
        # grad_sum += sum_j m*g_j equals B*mean_grad exactly, so that param-
        # sized accumulator is updated once per step OUTSIDE the loop — one
        # fewer parameter-sized loop carry (matters at 405B/1T scale). The
        # estimator statistic Q (moment: sum_j ||m*g_j||^2; exact/gram:
        # sum_i ||g_i||^2) is a scalar per microbatch and stays inside.
        # the model's step counters (an expert layer's routed rows) ride
        # the scan too: summed over microbatches, a ``max`` one maxed
        def micro_step(carry, mb):
            grads_acc, sq_sum, loss_acc, counts = carry
            (loss, metrics), grads = grad_fn(state.params, mb)
            grads_acc = jax.tree.map(
                lambda a, g: a + g.astype(a.dtype), grads_acc, grads
            )
            if diversity_on:
                sq_sum = sq_sum + _micro_sq_contrib(
                    state.params, mb, grads, micro_global
                )
            counts = {k: jnp.maximum(v, metrics[k]) if "max" in k else v + metrics[k]
                      for k, v in counts.items()}
            return (grads_acc, sq_sum, loss_acc + loss, counts), None

        grads0 = ptu.tree_zeros_like(state.params, dtype=grad_accum_dtype)
        zero = jnp.zeros((), jnp.float32)
        counts0 = {k: jnp.zeros((), jnp.int32) for k in counters}
        (grads_acc, sq_sum, loss_sum, counts), _ = jax.lax.scan(
            micro_step, (grads0, zero, zero, counts0), micro
        )
        grads = jax.tree.map(lambda g: (g / num_micro), grads_acc)

        div_state = state.div_state
        if diversity_on:
            b = jnp.float32(global_batch)
            div_state = diversity.DiversityState(
                grad_sum=jax.tree.map(
                    lambda acc, g: acc + b.astype(acc.dtype) * g.astype(acc.dtype),
                    div_state.grad_sum, grads,
                ),
                sq_norm_sum=div_state.sq_norm_sum + sq_sum,
                mb_count=div_state.mb_count + num_micro,
                sample_count=div_state.sample_count + b,
            )

        updates, opt_state = optimizer.update(grads, state.opt_state, state.params, lr)
        params = apply_updates(state.params, updates)
        new_state = TrainState(
            params=params, opt_state=opt_state, div_state=div_state,
            step=state.step + 1,
        )
        metrics = {
            "loss": loss_sum / num_micro,
            "grad_norm_sq": ptu.tree_sq_norm(grads),
            **counts,
        }
        return new_state, metrics

    return train_step


@functools.lru_cache(maxsize=None)
def _estimate_jit(estimator: str):
    return jax.jit(functools.partial(diversity.estimate, estimator=estimator))


@functools.lru_cache(maxsize=None)
def _reset_jit():
    return jax.jit(diversity.reset_state)


def epoch_end_host(state: TrainState, estimator: str = "moment") -> tuple[float, TrainState]:
    """Host-side epoch boundary: read the diversity estimate, reset the
    accumulators. Returns (Delta_hat, state-with-reset-accumulators).

    The jits are cached at module level — an epoch boundary costs one scalar
    device->host transfer, never a retrace."""
    delta = float(_estimate_jit(estimator)(state.div_state))
    reset = _reset_jit()(state.div_state)
    return delta, state._replace(div_state=reset)
