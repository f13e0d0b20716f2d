"""Sharding plans: which mesh axes carry which kind of parallelism.

A ``ShardingPlan`` names the mesh axes for the four parallelism kinds the
codebase uses:

  dp    data parallelism — the batch axis of inputs/activations.
  fsdp  parameter/optimizer-state sharding (ZeRO-style); usually the same
        axes as ``dp``, extended with 'pod' for models that do not fit HBM.
  tp    tensor parallelism — the hidden/vocab axis of matmul weights.
  ep    expert parallelism — the expert axis of MoE weights/buffers.

Model code never builds shardings directly.  The launch layer activates a
plan (plus a table of named activation PartitionSpecs) with ``use_plan``;
inside that context :func:`constrain` attaches ``with_sharding_constraint``
to the named activations, and :func:`shard_local` runs each Pallas kernel
once per shard (the compiler cannot partition a Mosaic kernel).  Outside
any plan — CPU smoke tests, benchmarks, single-host runs — ``constrain`` is
an EXACT no-op (returns its argument unchanged, inserts nothing into the
jaxpr), which is what lets the same model code run everywhere.

The active plan lives in a ``contextvars.ContextVar`` so nesting and
re-entrancy behave like lexical scoping, including across exceptions.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import math
from typing import Any, Callable, Iterator, Mapping

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

PyTree = Any

AxisNames = Any  # str | tuple[str, ...]


def make_mesh(axis_shapes: tuple[int, ...], axis_names: tuple[str, ...], *,
              devices=None) -> Mesh:
    """The repo's one mesh constructor: ``jax.make_mesh`` with Auto axes.

    ``jax.make_mesh`` defaults to Explicit axes, on which
    ``with_sharding_constraint`` (what :func:`constrain` attaches) is an
    error; every plan here relies on GSPMD propagation, i.e. Auto axes.
    """
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(AxisType.Auto,) * len(axis_names), devices=devices,
    )


@dataclasses.dataclass(frozen=True)
class ShardingPlan:
    """Mesh + axis assignment for dp/fsdp/tp/ep parallelism."""

    mesh: Any  # Mesh or AbstractMesh
    dp: tuple[str, ...] = ("data",)
    fsdp: tuple[str, ...] = ("data",)
    tp: AxisNames = "model"
    ep: tuple[str, ...] = ("data",)

    def axis_size(self, axes: AxisNames) -> int:
        """Total number of shards over ``axes`` (a name or tuple of names)."""
        if axes is None:
            return 1
        if isinstance(axes, str):
            axes = (axes,)
        return math.prod(self.mesh.shape[a] for a in axes)

    @property
    def dp_size(self) -> int:
        return self.axis_size(self.dp)

    @property
    def fsdp_size(self) -> int:
        return self.axis_size(self.fsdp)

    @property
    def tp_size(self) -> int:
        return self.axis_size(self.tp)

    @property
    def ep_size(self) -> int:
        return self.axis_size(self.ep)


# ---------------------------------------------------------------------------
# Active-plan context
# ---------------------------------------------------------------------------

_ACTIVE: contextvars.ContextVar[tuple[ShardingPlan, Mapping[str, P]] | None] = (
    contextvars.ContextVar("repro_dist_active_plan", default=None)
)


def current_plan() -> ShardingPlan | None:
    """The innermost active plan, or None outside every ``use_plan``."""
    active = _ACTIVE.get()
    return active[0] if active is not None else None


def current_act_specs() -> Mapping[str, P]:
    """The activation-spec table of the innermost active plan ({} if none)."""
    active = _ACTIVE.get()
    return active[1] if active is not None else {}


@contextlib.contextmanager
def use_plan(plan: ShardingPlan,
             act_specs: Mapping[str, P] | None = None) -> Iterator[ShardingPlan]:
    """Activate ``plan`` (with named activation specs) for the dynamic extent
    of the block.  Nests: the previous plan is restored on exit, also on
    exceptions."""
    token = _ACTIVE.set((plan, dict(act_specs or {})))
    try:
        yield plan
    finally:
        _ACTIVE.reset(token)


def _divisible_spec(shape: tuple[int, ...], spec: P, plan: ShardingPlan) -> P | None:
    """Drop spec entries whose axis product does not divide the dim.

    ``with_sharding_constraint`` rejects uneven shardings; activation names
    are shared across shapes (e.g. 'attn_q' applies to both the q-block and
    kv-block layouts), so per-dim divisibility is resolved at constrain time.
    Returns None when the spec has nothing to say about this shape.
    """
    entries = tuple(spec)
    if len(entries) != len(shape):
        return None
    fitted = []
    for dim, entry in zip(shape, entries):
        if entry is None or dim % plan.axis_size(entry) != 0:
            fitted.append(None)
        else:
            fitted.append(entry)
    if all(e is None for e in fitted):
        return None
    return P(*fitted)


def constrain(x: jax.Array, name: str) -> jax.Array:
    """Attach the activation sharding registered under ``name``, if any.

    Exact identity (the very same object, nothing added to the trace) when
    no plan is active, the name is not in the plan's spec table, or the spec
    cannot legally apply to ``x``'s shape.
    """
    active = _ACTIVE.get()
    if active is None:
        return x
    plan, specs = active
    spec = specs.get(name)
    if spec is None:
        return x
    fitted = _divisible_spec(tuple(x.shape), spec, plan)
    if fitted is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(plan.mesh, fitted))


def shard_local(fn: Callable, args: tuple, in_roles: tuple, out_roles: tuple):
    """``fn(*args)``, run once per shard of the active plan's mesh.

    The compiler cannot partition a Pallas TPU kernel ("Mosaic kernels cannot
    be automatically partitioned"), so under a plan the model calls each
    kernel through ``jax.shard_map``.  ``in_roles`` gives, per argument, the
    plan role of each dim: "dp" (the batch), "tp" (heads) or None (kept
    whole); an argument whose roles are None is whole on every shard.
    ``out_roles`` does the same for the single output.  A role whose axes do
    not divide every dim it labels is dropped, and those dims stay whole:
    still correct, at the cost of every shard computing them.  Outside a
    plan, or on a one-device mesh, this is the plain call.
    """
    plan = current_plan()
    if plan is None or plan.mesh.size == 1:
        return fn(*args)
    axes = {"dp": tuple(plan.dp) or None, "tp": plan.tp}
    ok = {role: axes[role] is not None for role in axes}
    for arg, roles in zip(args, in_roles):
        for dim, role in zip(arg.shape, roles or ()):
            if role is not None and dim % plan.axis_size(axes[role]):
                ok[role] = False

    def spec(roles) -> P:
        return P(*(axes[r] if r is not None and ok[r] else None
                   for r in (roles or ())))

    return jax.shard_map(
        fn, mesh=plan.mesh, in_specs=tuple(spec(r) for r in in_roles),
        out_specs=spec(out_roles), check_vma=False,
    )(*args)
