"""Elastic resharding (distributed/elastic.py) + the chain's fallback-mesh
shrink path (DESIGN.md §12 / §14 robustness satellite).

``validate_mesh_for_tree`` must report *every* leaf whose sharded dims
don't divide on the target mesh — naming the leaf path, the logical
axis and the mesh axes it maps to — because the forgiving pspec mapping
(``tree_pspecs``) silently replicates such dims, which is precisely the
failure a mesh shrink must not hide.  ``reshard_tree`` must move live
values exactly.  ``distributed_nn_chain_from_points(fallback_mesh=...)``
composes the two: exhausting the restart budget reshards the live state
onto the fallback and continues, or fails loudly naming offending axes.

Multi-device cases run in subprocesses with fake devices (see
conftest.run_with_devices), same as the distributed suites.
"""

import numpy as np
import pytest

from tests.conftest import run_with_devices


# ---------------------------------------------------------------- fast: p=1


def test_reshard_tree_none_shardings_is_identity():
    from repro.distributed.elastic import reshard_tree

    tree = {"a": np.arange(6.0), "b": (np.ones((2, 3)), 7)}
    out = reshard_tree(tree, {"a": None, "b": (None, None)})
    assert out["a"] is tree["a"] and out["b"][0] is tree["b"][0]
    assert out["b"][1] == 7


def test_validate_trivial_mesh_always_divides():
    import jax
    from jax.sharding import Mesh

    from repro.distributed.elastic import validate_mesh_for_tree
    from repro.distributed.sharding import ParamSpec

    mesh = Mesh(np.array(jax.devices()[:1]), ("p",))
    spec = {"W": ParamSpec((13, 7), ("rows", None))}
    assert validate_mesh_for_tree(spec, {"rows": ("p",)}, mesh) == []


# ------------------------------------------- slow: fake multi-device runs


@pytest.mark.slow
def test_validate_mesh_reports_offending_leaves_and_axes():
    run_with_devices("""
import numpy as np, jax
from jax.sharding import Mesh
from repro.distributed.elastic import validate_mesh_for_tree
from repro.distributed.sharding import ParamSpec

mesh3 = Mesh(np.array(jax.devices()[:3]), ("p",))
rules = {"rows": ("p",)}
spec = {
    "ok":  ParamSpec((12, 4), ("rows", None)),     # 12 % 3 == 0
    "bad": ParamSpec((10, 4), ("rows", None)),     # 10 % 3 != 0
    "rep": ParamSpec((10,), (None,)),              # unsharded: never flagged
}
problems = validate_mesh_for_tree(spec, rules, mesh3)
assert len(problems) == 1, problems
msg = problems[0]
# the message must name the leaf, the logical axis, and the mesh axes
assert "bad" in msg and "rows" in msg and "p" in msg and "10" in msg, msg
assert not any("ok" in p or "rep" in p for p in problems)

# a compatible mesh validates clean
mesh2 = Mesh(np.array(jax.devices()[:2]), ("p",))
assert validate_mesh_for_tree(spec, rules, mesh2) == []

# the forgiving pspec mapping would have hidden exactly this: it maps
# the non-dividing dim to replicated instead of reporting it
from repro.distributed.sharding import tree_pspecs
from jax.sharding import PartitionSpec as P
assert tree_pspecs(spec, rules, mesh3)["bad"] == P(None, None)
print("OK")
""", n_devices=4)


@pytest.mark.slow
def test_reshard_tree_moves_values_across_meshes():
    run_with_devices("""
import numpy as np, jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.distributed.elastic import reshard_tree

devs = jax.devices()
mesh4 = Mesh(np.array(devs[:4]), ("p",))
mesh2 = Mesh(np.array(devs[:2]), ("p",))
x = np.arange(8 * 3, dtype=np.float32).reshape(8, 3)
on4 = jax.device_put(x, NamedSharding(mesh4, P("p", None)))
moved = reshard_tree((on4,), (NamedSharding(mesh2, P("p", None)),))[0]
assert np.array_equal(np.asarray(moved), x)
assert moved.sharding.mesh.devices.size == 2
# each of the 2 shards holds 4 rows now
shard_shapes = {s.data.shape for s in moved.addressable_shards}
assert shard_shapes == {(4, 3)}, shard_shapes
print("OK")
""", n_devices=4)


@pytest.mark.slow
def test_chain_fallback_mesh_shrink_continues_exactly():
    run_with_devices("""
import numpy as np, jax
from jax.sharding import Mesh
from repro.core.nnchain import nn_chain_from_points
from repro.core.distributed import distributed_nn_chain_from_points
from repro.distributed.fault import SimulatedFailure
from repro.obs import get_registry

class FailKTimes:
    # unconditional failures: exhausts the restart budget on the first
    # mesh, then lets the resharded run proceed
    def __init__(self, k): self.k = k
    def check(self, step):
        if self.k > 0:
            self.k -= 1
            raise SimulatedFailure(f"injected ({self.k} left)")

rng = np.random.default_rng(5)
X = rng.normal(size=(40, 5)).astype(np.float32)
ser = np.asarray(nn_chain_from_points(X, "ward").merges)

fallback = Mesh(np.array(jax.devices()[:2]), ("p",))
events = []
before = get_registry().counter(
    "distributed_chain_shrinks_total", "").total()
res = distributed_nn_chain_from_points(
    X, "ward", segment_steps=10, max_restarts=1,
    failure_plan=FailKTimes(2), fallback_mesh=fallback,
    log=events.append)
# the shrink kept the committed state: merges are the serial chain's
assert np.array_equal(ser, np.asarray(res.merges))
assert any("resharding" in e and "p=2" in e for e in events), events
assert res.shards == 2          # the mesh the loop finished on
assert get_registry().counter(
    "distributed_chain_shrinks_total", "").total() == before + 1

# an incompatible fallback fails loudly, naming the offending axes,
# BEFORE any state moves
bad = Mesh(np.array(jax.devices()[:3]), ("p",))   # 40 % 3 != 0
try:
    distributed_nn_chain_from_points(
        X, "ward", segment_steps=10, max_restarts=1,
        failure_plan=FailKTimes(2), fallback_mesh=bad)
    raise AssertionError("expected RuntimeError")
except RuntimeError as e:
    assert "rows" in str(e) and "p=3" in str(e) and "40" in str(e), e

# without a fallback the exhaustion message stays diagnosable
try:
    distributed_nn_chain_from_points(
        X, "ward", segment_steps=10, max_restarts=1,
        failure_plan=FailKTimes(2))
    raise AssertionError("expected RuntimeError")
except RuntimeError as e:
    assert "max_restarts" in str(e) and "fallback_mesh" in str(e), e
print("OK")
""", n_devices=8)
