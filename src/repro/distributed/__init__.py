"""repro.distributed — state shardings, elastic resharding, fault simulation."""

from repro.distributed.elastic import reshard_tree, validate_mesh_for_tree
from repro.distributed.sharding import (
    AxisRules,
    ParamSpec,
    replicate,
    shard_rows,
    tree_pspecs,
    tree_shardings,
)

__all__ = [
    "AxisRules",
    "ParamSpec",
    "replicate",
    "reshard_tree",
    "shard_rows",
    "tree_pspecs",
    "tree_shardings",
    "validate_mesh_for_tree",
]
