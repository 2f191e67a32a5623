"""Sharded matrix-free NN-chain + two-phase tier (DESIGN.md §12).

Fast tests run in-process on the single real CPU device (p=1 collectives
are real, just degenerate); the cross-shard collectives, fault injection,
and Pallas row-tile route run in subprocesses with fake devices, same as
the distributed-LW suite.
"""

from pathlib import Path

import numpy as np
import pytest

from tests.conftest import run_with_devices

REPO = Path(__file__).resolve().parents[1]


def _mixture(n_per=24, k=6, d=5, seed=0, spread=20.0, noise=0.1):
    """Separated Gaussian mixture — merge structure is unambiguous, so the
    two-phase agreement gate measures approximation error, not tie luck."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(k, d)) * spread
    return np.concatenate(
        [c + noise * rng.normal(size=(n_per, d)) for c in centers]
    ).astype(np.float32)


# ---------------------------------------------------------------- fast: p=1


def test_sharded_chain_equals_serial_p1():
    """p=1 exercises the full shard_map program (psum/all_gather run for
    real) and must be bit-identical to the serial points chain."""
    from repro.core.distributed import distributed_nn_chain_from_points
    from repro.core.nnchain import nn_chain_from_points

    rng = np.random.default_rng(3)
    for n, method in ((41, "ward"), (30, "average"), (23, "weighted")):
        X = rng.normal(size=(n, 6)).astype(np.float32)
        ser = np.asarray(nn_chain_from_points(X, method).merges)
        dist = np.asarray(distributed_nn_chain_from_points(X, method).merges)
        assert np.array_equal(ser, dist), (n, method)


def test_cluster_api_distributed_route():
    X = _mixture()
    from repro.core.api import cluster

    ser = cluster(X, "ward", algorithm="nnchain", matrix_free=True)
    dist = cluster(X, "ward", algorithm="nnchain", backend="distributed")
    assert dist.backend == "distributed" and dist.algorithm == "nnchain"
    assert dist.distances is None           # never materialized
    assert np.array_equal(np.asarray(ser.merges), np.asarray(dist.merges))


def test_cluster_api_rejections():
    X = _mixture(n_per=8, k=3)
    D = ((X[:, None, :] - X[None, :, :]) ** 2).sum(-1)
    from repro.core.api import cluster

    # sharded chain needs the matrix-free capability
    with pytest.raises(ValueError, match="sharded matrix-free chain"):
        cluster(X, "single", algorithm="nnchain", backend="distributed")
    with pytest.raises(ValueError, match="sharded matrix-free chain"):
        cluster(X, "ward", algorithm="nnchain", backend="distributed",
                matrix_free=False)
    with pytest.raises(ValueError, match="sharded matrix-free chain"):
        cluster(D, "ward", metric="precomputed", algorithm="nnchain",
                backend="distributed")
    # two-phase is points-only too
    with pytest.raises(ValueError, match="twophase"):
        cluster(X, "complete", algorithm="twophase")
    with pytest.raises(ValueError, match="twophase"):
        cluster(D, "ward", metric="precomputed", algorithm="twophase")


def test_mesh_validation_multi_axis():
    import jax
    from jax.sharding import Mesh

    from repro.core.distributed import require_ring_mesh

    bad = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("a", "b"))
    with pytest.raises(ValueError, match="1-D mesh"):
        require_ring_mesh(bad)
    ok = require_ring_mesh(None)
    assert len(ok.axis_names) == 1


def test_pad_to_mesh():
    from repro.core.distributed import pad_to_mesh

    assert pad_to_mesh(10, 4) == 12
    assert pad_to_mesh(12, 4) == 12
    assert pad_to_mesh(10, 4, block=8) == 32
    assert pad_to_mesh(0, 4) == 4          # at least one row per shard
    with pytest.raises(ValueError):
        pad_to_mesh(10, 0)
    with pytest.raises(ValueError):
        pad_to_mesh(10, 2, block=0)


# ------------------------------------------------------------- two-phase


def test_two_phase_valid_and_agrees_on_separated_data():
    from repro.core import dendrogram as dg
    from repro.core.distributed import two_phase_from_points
    from repro.core.nnchain import nn_chain_from_points

    X = _mixture(n_per=32, k=8, d=6, seed=1)
    n = len(X)
    res = two_phase_from_points(X, "ward", shards=4)
    merges = np.asarray(res.merges)
    assert int(res.n_merges) == n - 1
    dg.validate_merges(merges, n=n)
    # heights survived the monotone repair in sorted order
    assert np.all(np.diff(merges[:, 2]) >= 0)

    exact = dg.canonical_order(
        np.asarray(nn_chain_from_points(X, "ward").merges), n=n
    )
    agr = dg.merge_set_agreement(exact, merges, n=n)
    # well-separated mixture: the shard truncation level sits far above
    # the cluster scale, so agreement should be near-perfect.  The gate
    # is deliberately conservative; the *measured* value is reported by
    # bench_distributed / EXPERIMENTS §Perf-7.
    assert agr >= 0.5, agr

    # the k-cut recovers the mixture components exactly
    lab_e = dg.cut(exact, 8, n=n)
    lab_t = dg.cut(merges, 8, n=n)
    part = lambda lab: {frozenset(np.where(lab == c)[0]) for c in set(lab)}
    assert part(lab_e) == part(lab_t)


def test_two_phase_api_route():
    from repro.core import dendrogram as dg
    from repro.core.api import cluster

    X = _mixture(n_per=16, k=4, seed=2)
    res = cluster(X, "ward", algorithm="twophase")
    assert res.algorithm == "twophase"
    dg.validate_merges(np.asarray(res.merges), n=len(X))
    assert len(res.labels(4)) == len(X)


def test_merge_set_agreement():
    from repro.core import dendrogram as dg

    a = np.array([[0, 1, 1.0, 2], [2, 3, 2.0, 2], [0, 2, 3.0, 4]],
                 dtype=np.float32)
    assert dg.merge_set_agreement(a, a.copy(), n=4) == 1.0
    b = np.array([[0, 2, 1.0, 2], [1, 3, 2.0, 2], [0, 1, 3.0, 4]],
                 dtype=np.float32)
    # only the root {0,1,2,3} leafset is shared
    assert dg.merge_set_agreement(a, b, n=4) == pytest.approx(1 / 3)


# ------------------------------------------- slow: real cross-shard runs


@pytest.mark.slow
def test_sharded_chain_equals_serial_multidevice():
    run_with_devices("""
import numpy as np, jax
from repro.core.nnchain import nn_chain_from_points
from repro.core.distributed import distributed_nn_chain_from_points
assert jax.device_count() == 8
rng = np.random.default_rng(7)
for n, method in ((41, "ward"), (64, "average"), (37, "weighted")):
    X = rng.normal(size=(n, 6)).astype(np.float32)
    ser = np.asarray(nn_chain_from_points(X, method).merges)
    dist = np.asarray(distributed_nn_chain_from_points(X, method).merges)
    assert np.array_equal(ser, dist), (n, method)
print("OK")
""")


@pytest.mark.slow
@pytest.mark.parametrize("n, d", [(8192, 128), (4099, 256)])
def test_cluster_sharded_route_equals_staged_serial_route(n, d):
    """``cluster``'s sharded chain on four devices against its one-device
    chain at an n where that chain stages (2 stages at the default
    floor; 4099 leaves a padded row on the mesh): the same merges and
    trips, the benchmark's float64 reference within the limits of its
    ``back-to-back`` mix, four shards and one stage on the result and
    the registry, and one ``sharded_chain/place`` and one
    ``sharded_chain/loop`` phase per call inside its ``cluster/engine``."""
    run_with_devices(f"""
import json, sys
sys.path.insert(0, {str(REPO)!r})
import numpy as np, jax
from chipbench.generate import gaussian_mixture, rng_for
from chipbench.reference import Tally, judge, reference_tree
from repro.core import cluster
from repro.obs import PHASE_SECONDS, get_registry
assert jax.device_count() == 4
X = gaussian_mixture(rng_for(2**31 + 11, 0), {n}, {d}, 16)
one = cluster(X, "ward", backend="serial", keep_inputs=False)
four = cluster(X, "ward", algorithm="nnchain", backend="distributed",
               keep_inputs=False)
assert one.chain_stages == 2 and one.chain_shards == 1, one
assert four.chain_stages == 1 and four.chain_shards == 4, four
assert np.array_equal(one.merges, four.merges)
assert four.chain_trips == one.chain_trips
tally = Tally()
tally.add(four.merges, reference_tree(X, "ward"))
limits = json.load(open({str(REPO / "chipbench/traffic/back-to-back.json")!r}))
ok, checks = judge(tally.numbers(), limits["limits"])
assert ok, checks
reg = get_registry()
assert reg.get("chain_shards").window() == [1, 4]
hist = reg.get(PHASE_SECONDS)
(engine,) = hist.window(phase="cluster/engine")[1:]
for p in ("sharded_chain/place", "sharded_chain/loop"):
    (t,) = hist.window(phase=p)
    assert 0 < t < engine, (p, t, engine)
print("OK")
""", n_devices=4)


@pytest.mark.slow
def test_sharded_chain_pallas_row_tiles():
    run_with_devices("""
import numpy as np
from repro.core import dendrogram as dg
from repro.core.nnchain import nn_chain_from_points
from repro.core.distributed import distributed_nn_chain_from_points
rng = np.random.default_rng(11)
X = rng.normal(size=(57, 6)).astype(np.float32)
ser = dg.canonical_order(np.asarray(nn_chain_from_points(X, "ward").merges), n=57)
dist = dg.canonical_order(np.asarray(distributed_nn_chain_from_points(
    X, "ward", use_pallas=True, block_n=128, interpret=True).merges), n=57)
assert np.allclose(ser[:, :2], dist[:, :2])
assert np.allclose(ser[:, 2], dist[:, 2], rtol=1e-4, atol=1e-5)
print("OK")
""", n_devices=2)


@pytest.mark.slow
def test_fault_injection_recovers_and_exhausts():
    run_with_devices("""
import numpy as np
from repro.core.nnchain import nn_chain_from_points
from repro.core.distributed import distributed_nn_chain_from_points
from repro.distributed.fault import FailurePlan, StepDeadline
rng = np.random.default_rng(5)
X = rng.normal(size=(40, 5)).astype(np.float32)
ser = np.asarray(nn_chain_from_points(X, "ward").merges)

# 1. a dropped shard mid-run: the segmented driver retries the segment
#    from the committed on-device state and the result stays exact
events = []
res = distributed_nn_chain_from_points(
    X, "ward", segment_steps=10,
    failure_plan=FailurePlan(fail_at=(1,)), log=events.append)
assert np.array_equal(ser, np.asarray(res.merges))
assert any("retrying segment" in e for e in events), events
# telemetry rides on the result (DESIGN.md §13), not just the log
assert res.restarts == 1 and res.stragglers == 0 and res.segments == 4, res

# 2. a shard that never comes back: diagnosable error, not a hang
class AlwaysFail:
    def check(self, step):
        from repro.distributed.fault import SimulatedFailure
        raise SimulatedFailure(f"injected at step {step}")
try:
    distributed_nn_chain_from_points(
        X, "ward", segment_steps=10, failure_plan=AlwaysFail(),
        max_restarts=2, log=events.append)
    raise AssertionError("expected RuntimeError")
except RuntimeError as e:
    assert "max_restarts" in str(e) and "committed" in str(e), e

# 3. a straggling segment is flagged but the run completes exactly
events = []
res = distributed_nn_chain_from_points(
    X, "ward", segment_steps=10,
    deadline=StepDeadline(factor=0.0, warmup=1), log=events.append)
assert np.array_equal(ser, np.asarray(res.merges))
assert any("straggled" in e for e in events), events
assert res.stragglers >= 1 and res.restarts == 0, res
print("OK")
""", n_devices=2)
