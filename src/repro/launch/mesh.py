"""Production mesh + sharding-plan construction.

``make_production_mesh`` is a FUNCTION (never module-level state) so that
importing this module touches no jax device state — required because the
dry-run process forces 512 host devices via XLA_FLAGS *before* first jax use,
while tests/benches must keep seeing 1 device.
"""

from __future__ import annotations

import jax

from repro.dist.plan import ShardingPlan, make_mesh

GIB = 1 << 30


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_plan(mesh, *, param_bytes: int | None = None,
              fsdp_pod_threshold: int = 2 * GIB) -> ShardingPlan:
    """Build the sharding plan for a mesh.

    On the multi-pod mesh the 'pod' axis always carries data parallelism; it
    is ALSO added to the FSDP/EP axes when the model would otherwise exceed
    ``fsdp_pod_threshold`` parameter bytes per chip (ZeRO across pods trades
    DCN all-gathers for fitting 405B/1T-scale states in 16 GB HBM).
    """
    axes = mesh.axis_names
    if "pod" in axes:
        dp = ("pod", "data")
        fsdp: tuple[str, ...] = ("data",)
        ep: tuple[str, ...] = ("data",)
        if param_bytes is not None:
            chips = mesh.devices.size
            per_chip = param_bytes / (mesh.shape["data"] * mesh.shape["model"])
            if per_chip > fsdp_pod_threshold:
                fsdp = ("pod", "data")
                ep = ("pod", "data")
        return ShardingPlan(mesh=mesh, dp=dp, fsdp=fsdp, tp="model", ep=ep)
    return ShardingPlan(mesh=mesh, dp=("data",), fsdp=("data",), tp="model", ep=("data",))
