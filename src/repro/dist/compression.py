"""Error-feedback gradient compression for cross-pod (DCN) reductions.

Between pods the all-reduce crosses data-center networking, ~20x slower per
byte than ICI — compressing the pod-level gradient exchange int8 cuts that
traffic 4x vs f32 at a quantization error that error feedback (Seide et al.
2014; Karimireddy et al. 2019 "EF signSGD") keeps from accumulating: the
residual of each round is carried into the next round's quantizer input, so
the TRANSMITTED signal integrates to the true signal over time.

``compress_leaf``         one leaf: absmax-scaled int8 quantize of
                          (grad + carried error), returning the dequantized
                          transmit value and the new error residual.
``compressed_pod_mean``   runs INSIDE ``shard_map``: quantizes local leaves,
                          all-gathers the int8 payload + f32 scale over the
                          pod axis (the compressed wire format), and returns
                          the dequantized mean plus the new error state.
``make_compressed_pod_mean``  wraps the above in ``shard_map`` over a mesh
                          axis for callers that hold unsharded trees.

Production caller: ``repro.pod.step.make_pod_train_step`` — the train step
``PodLadder`` compiles on every cross-pod (``pods > 1``) elastic rung calls
``compressed_pod_mean`` inside its shard_map for the DCN gradient exchange,
with the error-feedback residuals threaded through ``TrainState.err_state``
(installed / re-zeroed per rung by ``PodLadder.adapt_state``).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

PyTree = Any

_QMAX = 127.0


def init_error_state(grads: PyTree) -> PyTree:
    """Zero f32 residuals, one per gradient leaf (local-shard shapes)."""
    return jax.tree.map(lambda g: jnp.zeros(jnp.shape(g), jnp.float32), grads)


def _quantize(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-tensor absmax int8: returns (q int8, scale f32 scalar)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / _QMAX
    q = jnp.clip(jnp.round(x / scale), -_QMAX, _QMAX).astype(jnp.int8)
    return q, scale


def compress_leaf(g: jax.Array, err: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Quantize one gradient leaf with error feedback.

    Returns ``(dequantized, new_err)``: ``dequantized`` is what the wire
    carries (reconstructed to g's dtype), ``new_err`` the f32 residual to
    feed back next round.  Works on any shape including scalars.
    """
    x = g.astype(jnp.float32) + err.astype(jnp.float32)
    q, scale = _quantize(x)
    deq = q.astype(jnp.float32) * scale
    return deq.astype(g.dtype), x - deq


def compressed_pod_mean(
    grads: PyTree, err: PyTree, axis_name: str
) -> tuple[PyTree, PyTree]:
    """Compressed mean over a shard_map axis (call inside ``shard_map``).

    Each shard quantizes its local leaves (folding in the carried error),
    all-gathers the int8 tensors and their scalar scales over ``axis_name``
    — the only cross-pod bytes are the compressed payload — and dequantizes
    and averages locally.  Returns ``(mean_tree, new_err_tree)``; the error
    state stays shard-local.
    """
    leaves, treedef = jax.tree.flatten(grads)
    err_leaves = jax.tree.leaves(err)
    assert len(leaves) == len(err_leaves), "grads/err tree mismatch"

    means, new_errs = [], []
    for g, e in zip(leaves, err_leaves):
        x = g.astype(jnp.float32) + e.astype(jnp.float32)
        q, scale = _quantize(x)
        new_errs.append(x - q.astype(jnp.float32) * scale)
        q_all = jax.lax.all_gather(q, axis_name)  # (pods, ...)
        s_all = jax.lax.all_gather(scale, axis_name)  # (pods,)
        deq = q_all.astype(jnp.float32) * s_all.reshape((-1,) + (1,) * jnp.ndim(g))
        means.append(jnp.mean(deq, axis=0).astype(g.dtype))
    return treedef.unflatten(means), treedef.unflatten(new_errs)


def make_compressed_pod_mean(mesh, axis_name: str):
    """A jittable ``(grads, err) -> (mean, new_err)`` over stacked trees.

    Both ``grads`` and ``err`` carry a leading pod axis (length = the mesh
    axis size) and are sharded over ``axis_name``; build ``err`` as
    ``init_error_state`` of the stacked gradients.  The mean comes back
    replicated; the residuals stay PER-POD (sharded over ``axis_name``) —
    each pod's next round folds in its own residual, which is what makes
    the error-feedback accumulation argument hold.
    """

    def fn(grads: PyTree, err: PyTree):
        red, new_err = compressed_pod_mean(
            jax.tree.map(lambda g: g[0], grads),
            jax.tree.map(lambda e: e[0], err),
            axis_name,
        )
        return red, jax.tree.map(lambda e: e[None], new_err)

    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name)),
        out_specs=(P(), P(axis_name)),
        check_vma=False,
    )
