"""Every `cluster` route against scipy's `linkage`, an oracle outside repro.

The reference is `pdist` then `linkage` in float64, under the convention
the program keeps for its inputs:

* ward, centroid and median work on squared Euclidean distances (their
  Lance-Williams recurrences are exact there), while scipy takes plain
  Euclidean distances and reports Euclidean heights: its heights are
  squared before they are compared;
* average and weighted under ``metric="sqeuclidean"`` (the matrix-free
  chain's convention) take squared distances on both sides;
* everything else takes plain Euclidean distances on both sides.

A route conforms when its linkage matrix equals scipy's ``Z`` in the two
id columns and the size column, and its heights agree within rtol 1e-4.
The data is Gaussian-mixture points (`repro.data.synthetic`), which have
no tied distances, so the tree is unique.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest
from scipy.cluster.hierarchy import is_valid_linkage, linkage
from scipy.spatial.distance import pdist, squareform

from repro.core import cluster, cluster_batch
from repro.core.dendrogram import to_linkage_matrix
from repro.data.synthetic import gaussian_mixture
from tests.conftest import run_with_devices

METHODS = ("single", "complete", "average", "weighted", "centroid",
           "median", "ward")
REDUCIBLE = ("single", "complete", "average", "weighted", "ward")
POINTS = ("ward", "average", "weighted")
SQUARED_HEIGHTS = ("ward", "centroid", "median")


def scipy_z(X, method, metric=None):
    """scipy's linkage matrix of points ``X`` under the convention above."""
    X = np.asarray(X, np.float64)
    if metric == "sqeuclidean" and method not in SQUARED_HEIGHTS:
        return linkage(pdist(X, "sqeuclidean"), method)
    Z = linkage(pdist(X), method)
    if method in SQUARED_HEIGHTS:
        Z[:, 2] **= 2
    return Z


def program_matrix(X, method):
    """The distance matrix the program is handed for ``method``."""
    D = squareform(pdist(np.asarray(X, np.float64)))
    return (D**2 if method in SQUARED_HEIGHTS else D).astype(np.float32)


def assert_conforms(merges, Z, n):
    """``merges`` (slot convention) is scipy's tree ``Z``, or its first
    rows for an early-stopped run (scipy validates only whole trees)."""
    got = to_linkage_matrix(np.asarray(merges), n)
    assert got.shape == Z.shape
    assert got.shape[0] < n - 1 or is_valid_linkage(got)
    np.testing.assert_array_equal(got[:, [0, 1, 3]], Z[:, [0, 1, 3]])
    np.testing.assert_allclose(got[:, 2], Z[:, 2], rtol=1e-4)


def points(n=48, d=8, seed=0):
    return gaussian_mixture(seed, n, d, k=4, return_labels=False)


# ------------------------------------------------------------ matrix input


LW_ROUTES = {
    "baseline": dict(variant="baseline"),
    "rowmin": dict(variant="rowmin"),
    "lazy": dict(variant="lazy"),
    "kernel": dict(backend="kernel"),
}


@pytest.mark.parametrize("method", METHODS)
@pytest.mark.parametrize("route", list(LW_ROUTES))
def test_lw_on_matrix(route, method):
    X = points()
    res = cluster(program_matrix(X, method), method, algorithm="lw",
                  **LW_ROUTES[route])
    assert res.algorithm == "lw"
    assert_conforms(res.merges, scipy_z(X, method), len(X))


@pytest.mark.parametrize("method", REDUCIBLE)
def test_nnchain_on_matrix(method):
    X = points()
    res = cluster(program_matrix(X, method), method, algorithm="nnchain")
    assert res.algorithm == "nnchain"
    assert_conforms(res.merges, scipy_z(X, method), len(X))


@pytest.mark.parametrize("method", METHODS)
def test_cluster_batch_ragged(method):
    batch = [points(n, seed=s) for s, n in enumerate((48, 31, 17))]
    out = cluster_batch([program_matrix(X, method) for X in batch], method)
    assert len(out) == len(batch)
    for X, res in zip(batch, out):
        assert_conforms(res.merges, scipy_z(X, method), len(X))


# ------------------------------------------------------------ points input


@pytest.mark.parametrize("method", METHODS)
def test_default_routing_on_points(method):
    X = points()
    res = cluster(X, method)
    assert_conforms(res.merges, scipy_z(X, method), len(X))


@pytest.mark.parametrize("method", POINTS)
def test_matrix_free_chain(method):
    X = points()
    res = cluster(X, method, metric="sqeuclidean", matrix_free=True)
    assert res.algorithm == "nnchain" and res.distances is None
    assert_conforms(res.merges, scipy_z(X, method, "sqeuclidean"), len(X))


@pytest.mark.parametrize("method", POINTS)
def test_staged_matrix_free_chain(method):
    """256 × 4096 float32 summaries are 4 MiB, twice the staging floor
    (`CHAIN_STAGE_MIN_BYTES`), so the chain runs in two stages."""
    X = points(n=256, d=4096)
    res = cluster(X, method, metric="sqeuclidean", matrix_free=True,
                  keep_inputs=False)
    assert res.chain_stages >= 2
    assert_conforms(res.merges, scipy_z(X, method, "sqeuclidean"), len(X))


@pytest.mark.parametrize("algorithm,method",
                         [("lw", "complete"), ("nnchain", "ward")])
def test_stop_at_k(algorithm, method):
    X, k = points(), 5
    res = cluster(program_matrix(X, method), method, algorithm=algorithm,
                  stop_at_k=k)
    assert res.n_merges == len(X) - k
    assert_conforms(res.merges, scipy_z(X, method)[: len(X) - k], len(X))


# ------------------------------------------- four fake CPU devices


SHARDED = [("chain", m) for m in POINTS] + [("lw", "complete"),
                                            ("lw", "ward")]


@pytest.fixture(scope="module")
def sharded_merges():
    """The sharded routes' merges, all from one child on four devices."""
    out = run_with_devices(f"""
import json
import numpy as np
from repro.core import cluster
from repro.data.synthetic import gaussian_mixture
X = gaussian_mixture(0, 48, 8, k=4, return_labels=False)
out = {{}}
for engine, method in {SHARDED!r}:
    if engine == "chain":
        res = cluster(X, method, metric="sqeuclidean", algorithm="nnchain",
                      backend="distributed")
        assert res.chain_shards == 4, res.chain_shards
    else:
        res = cluster(X, method, algorithm="lw", backend="distributed")
    assert res.backend == "distributed"
    out[engine + "/" + method] = np.asarray(res.merges).tolist()
print(json.dumps(out))
""", n_devices=4)
    return json.loads(out.strip().splitlines()[-1])


@pytest.mark.parametrize("engine,method", SHARDED)
def test_sharded_on_four_devices(sharded_merges, engine, method):
    X = points()
    metric = "sqeuclidean" if engine == "chain" else None
    assert_conforms(sharded_merges[f"{engine}/{method}"],
                    scipy_z(X, method, metric), len(X))


# ------------------------------------------------------- the CLI driver


@pytest.mark.parametrize("mode", ["embed", "rmsd"])
def test_cluster_run_cli(mode):
    n = 40
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    src = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.cluster_run", "--mode", mode,
         "--n", str(n), "--dim", "8", "--atoms", "12", "--k", "4"],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr
    assert f"] {n - 1} merges in " in out.stdout, out.stdout
