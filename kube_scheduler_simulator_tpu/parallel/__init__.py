"""Device-mesh parallelism: mesh construction, cluster-array shardings,
and Monte-Carlo weight sweeps (SURVEY.md §2 parallelism table)."""

from .mesh import build_mesh, surviving_mesh
from .shard import NODE_AXIS_FIELDS, encoded_shardings, shard_encoded
from .sweep import GangSweep, WeightSweep, weights_for

__all__ = [
    "build_mesh",
    "surviving_mesh",
    "shard_encoded",
    "encoded_shardings",
    "NODE_AXIS_FIELDS",
    "WeightSweep",
    "GangSweep",
    "weights_for",
]
