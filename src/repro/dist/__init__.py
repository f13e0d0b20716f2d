"""Distribution layer: sharding plans, PartitionSpec inference, compression.

``plan``         ShardingPlan (mesh + dp/fsdp/tp/ep axis assignment), the
                 ``use_plan`` context and the ``constrain`` activation hook
                 that models call without knowing whether a plan is active.
``sharding``     divisibility-aware PartitionSpec inference for parameter /
                 optimizer-state / batch / KV-cache trees.
``compression``  error-feedback int8 gradient compression and the compressed
                 cross-pod mean used on DCN-connected meshes.
"""

from repro.dist import compression, plan, sharding
from repro.dist.plan import (
    ShardingPlan,
    constrain,
    current_plan,
    make_mesh,
    use_plan,
)
from repro.dist.sharding import (
    batch_pspecs,
    cache_pspecs,
    infer_pspecs,
    shardings_of,
)

__all__ = [
    "ShardingPlan",
    "constrain",
    "current_plan",
    "make_mesh",
    "use_plan",
    "infer_pspecs",
    "batch_pspecs",
    "cache_pspecs",
    "shardings_of",
    "plan",
    "sharding",
    "compression",
]
