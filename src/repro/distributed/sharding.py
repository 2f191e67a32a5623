"""Declared shapes and shardings of the clustering engines' device state.

A :class:`ParamSpec` names each dim of a state leaf with a *logical* axis
(``"rows"`` for the sharded points/summary rows, ``None`` for a
replicated dim); a rule set maps the logical names onto mesh axes.
:func:`tree_shardings` turns a spec tree into ``NamedSharding``s for a
mesh, and :mod:`repro.distributed.elastic` validates and reshards a live
tree against them.  :func:`shard_rows` and :func:`replicate` place the
arrays of the paper engines' data layout (DESIGN.md §12).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Mapping

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisRules = Mapping[str, tuple[str, ...] | None]


@dataclass(frozen=True)
class ParamSpec:
    """Declared state leaf: its shape and one logical axis per dim."""

    shape: tuple[int, ...]
    axes: tuple[str | None, ...]

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} vs axes {self.axes}")


def tree_pspecs(spec_tree, rules: AxisRules, mesh: Mesh):
    """Map a tree of ParamSpec (anything with ``.axes``) to PartitionSpecs."""

    def one(spec):
        if isinstance(spec, ParamSpec):
            # validate divisibility; drop shardings that don't divide evenly
            parts: list[Any] = []
            used: set[str] = set()
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            for dim, ax in zip(spec.shape, spec.axes):
                phys = rules.get(ax) if ax else None
                if not phys:
                    parts.append(None)
                    continue
                keep = tuple(
                    p for p in phys if p in sizes and p not in used
                )
                total = int(np.prod([sizes[p] for p in keep])) if keep else 1
                if keep and dim % total == 0:
                    used.update(keep)
                    parts.append(keep if len(keep) > 1 else keep[0])
                else:
                    parts.append(None)
            return P(*parts)
        return P()

    return jax.tree.map(one, spec_tree, is_leaf=lambda s: hasattr(s, "axes"))


def tree_shardings(spec_tree, rules: AxisRules, mesh: Mesh):
    return jax.tree.map(
        lambda p: NamedSharding(mesh, p),
        tree_pspecs(spec_tree, rules, mesh),
        is_leaf=lambda s: isinstance(s, P),
    )


# ---------------------------------------------------------------------------
# clustering points sharding (the paper engines' data layout, DESIGN.md §12)
# ---------------------------------------------------------------------------


def shard_rows(arr, mesh: Mesh):
    """Block-row shard an array's leading dim over the 1-D clustering mesh.

    The layout every sharded clustering engine consumes: shard ``s`` of
    ``p`` owns rows ``[s·m/p, (s+1)·m/p)`` — the dense LW engine's
    ``(n, n)`` matrix rows, and the matrix-free chain engine's ``(n, d)``
    points/summaries.  The leading dim must divide the mesh size
    (:func:`repro.core.distributed.pad_to_mesh` computes the padded
    size in one place).
    """
    spec = P(mesh.axis_names[0], *([None] * (np.ndim(arr) - 1)))
    return jax.device_put(arr, NamedSharding(mesh, spec))


def replicate(arr, mesh: Mesh):
    """Replicate a bookkeeping array on every device of the mesh.

    The matrix-free chain engine keeps its O(n) state (scatter terms,
    liveness, sizes, the chain stack, the merge list) replicated — that
    is the ``+ n`` in its O(n·d/p + n) per-device storage accounting."""
    return jax.device_put(arr, NamedSharding(mesh, P()))
