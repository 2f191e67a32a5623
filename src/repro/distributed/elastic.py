"""Elastic resharding: move a live state tree onto a different mesh.

The sharded chain's fallback-mesh shrink (DESIGN.md §12) validates its
state against the smaller mesh, so a bad target fails loudly before any
state is touched, then places every leaf with the new mesh's shardings
(device→device via the host).
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh


def validate_mesh_for_tree(spec_tree, rules, mesh: Mesh) -> list[str]:
    """Return a list of leaves whose sharded dims don't divide on ``mesh``
    (empty = mesh is valid for this state tree).

    Maps each leaf's logical axes through ``rules`` directly rather than
    via ``tree_pspecs`` — the pspec mapping *silently replicates* a dim
    that doesn't divide, which is exactly the failure this validator
    exists to surface: a mesh shrink that would quietly turn a sharded
    leaf into a replicated one must fail loudly, naming the leaf, the
    offending logical axis and the mesh axes it maps to.
    """
    problems = []
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    flat = jax.tree_util.tree_flatten_with_path(
        spec_tree, is_leaf=lambda s: hasattr(s, "axes"))[0]
    for path, spec in flat:
        used: set[str] = set()
        for dim, ax in zip(spec.shape, spec.axes):
            phys = rules.get(ax) if ax else None
            keep = tuple(
                p for p in (phys or ()) if p in sizes and p not in used
            )
            if not keep:
                continue
            total = int(np.prod([sizes[a] for a in keep]))
            if dim % total:
                problems.append(
                    f"{jax.tree_util.keystr(path) or '<root>'}: dim {dim} "
                    f"(logical axis {ax!r} -> mesh axes {keep}, size "
                    f"{total}) does not divide"
                )
            else:
                used.update(keep)
    return problems


def reshard_tree(tree: Any, shardings: Any) -> Any:
    """Place every leaf according to ``shardings`` (host round-trip)."""

    def one(x, sh):
        if sh is None:
            return x
        host = np.asarray(jax.device_get(x)) if hasattr(x, "shape") else x
        return jax.device_put(host, sh)

    return jax.tree.map(one, tree, shardings)
