"""Exact state movement between ladder rungs.

``reshard`` moves a full ``TrainState`` (params, optimizer state, diversity
accumulators, compression error-feedback — any pytree) from one rung's plan
onto another's: every leaf is ``device_put`` onto the destination plan's
*inferred* sharding (``dist.sharding.infer_pspecs``, the same suffix rules
the dry-run uses), so optimizer/diversity mirrors land exactly where their
parameters do.  The transfer is value-exact — no arithmetic, no
re-materialisation — and donation-friendly: with ``donate=True`` the source
buffers may be reused for the destination (the steady state during a rung
transition is one state plus the in-flight copies, not two full states).

When source and destination describe the same rung (``same_plan``), the
function is a STRICT no-op: it returns the identical state object and
issues no transfers at all — the Trainer calls it unconditionally at every
epoch boundary.

``place`` is the restore-time variant: it puts a freshly-loaded host
(numpy) tree onto a plan's inferred shardings — or plain single-device jax
arrays when no plan is active.  The checkpoint layer reuses it
(``CheckpointManager.restore(plan=...)``): checkpoints store logical host
tensors, so a state saved on one rung resumes on any other.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.dist.plan import ShardingPlan
from repro.dist.sharding import infer_pspecs, shardings_of

PyTree = Any


def same_mesh(a, b) -> bool:
    """True when two meshes span the same devices under the same axis
    layout (AbstractMeshes compare by shape/names only — they have none)."""
    if a is b:
        return True
    if a is None or b is None:
        return False
    if tuple(a.axis_names) != tuple(b.axis_names):
        return False
    da, db = getattr(a, "devices", None), getattr(b, "devices", None)
    if da is None or db is None:
        return da is None and db is None and dict(a.shape) == dict(b.shape)
    return da.shape == db.shape and all(
        x.id == y.id for x, y in zip(da.flat, db.flat)
    )


def same_plan(a: ShardingPlan | None, b: ShardingPlan | None) -> bool:
    """True when two plans are the same rung: same mesh, same axis roles."""
    if a is b:
        return True
    if a is None or b is None:
        return False
    return (
        a.dp == b.dp
        and a.fsdp == b.fsdp
        and a.tp == b.tp
        and a.ep == b.ep
        and same_mesh(a.mesh, b.mesh)
    )


def state_shardings(tree: PyTree, plan: ShardingPlan) -> PyTree:
    """NamedShardings for ``tree`` on ``plan`` via the suffix inference rules
    (optimizer/diversity accumulators shard exactly like their parameters;
    unmatched leaves — small-model params, scalars — replicate)."""
    return shardings_of(infer_pspecs(tree, plan), plan)


def reshard(
    state: PyTree,
    src_plan: ShardingPlan | None,
    dst_plan: ShardingPlan | None,
    *,
    donate: bool = True,
) -> PyTree:
    """Move ``state`` from ``src_plan``'s rung onto ``dst_plan``'s.

    Strict no-op (the very same object, zero transfers) when the rung is
    unchanged.  ``dst_plan=None`` gathers onto the default device (the
    single-device regime).  Donation invalidates the source buffers on
    backends that support aliasing — callers must hold only the returned
    state, exactly as with engine steps.
    """
    if same_plan(src_plan, dst_plan):
        return state
    if dst_plan is None:
        return jax.device_put(state, jax.devices()[0], donate=donate)
    return jax.device_put(state, state_shardings(state, dst_plan), donate=donate)


def place(tree: PyTree, plan: ShardingPlan | None) -> PyTree:
    """Put a host (or device) tree onto ``plan``'s inferred shardings; plain
    single-device jax arrays when ``plan`` is None.  The checkpoint-restore
    path: logical host tensors -> whatever rung is live."""
    if plan is None:
        return jax.tree.map(jnp.asarray, tree)
    return jax.device_put(tree, state_shardings(tree, plan))
