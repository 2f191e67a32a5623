"""End-to-end behaviour tests for the paper's system.

The top-level contract: raw objects in → correct hierarchy out, on every
backend (tests/test_scipy_conformance.py checks every route against scipy).
"""

import numpy as np

from repro.core import cluster
from repro.data.synthetic import conformations, gaussian_mixture


def _purity(labels, truth, k):
    p = 0
    for c in range(k):
        m = truth[labels == c]
        if len(m):
            p += np.bincount(m).max()
    return p / len(truth)


def test_cluster_api_recovers_mixture_serial():
    X, y = gaussian_mixture(0, 120, 8, k=4)
    res = cluster(X, method="complete", backend="serial")
    assert _purity(res.labels(4), y, 4) > 0.9


def test_cluster_api_kernel_backend():
    X, y = gaussian_mixture(1, 80, 8, k=4)
    res = cluster(X, method="complete", backend="kernel")
    ser = cluster(X, method="complete", backend="serial")
    np.testing.assert_array_equal(res.merges[:, :2], ser.merges[:, :2])


def test_protein_pipeline_end_to_end():
    """The paper's motivating application: conformations → RMSD → LW tree."""
    C, y = conformations(0, 36, 16, k=3, noise=0.05)
    res = cluster(C, method="complete", metric="rmsd", backend="serial")
    assert _purity(res.labels(3), y, 3) > 0.9
