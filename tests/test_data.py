"""Synthetic data: determinism and clusterable generators."""

import numpy as np
import pytest

from repro.data.synthetic import conformations, gaussian_mixture


def test_gaussian_mixture_separable():
    X, y = gaussian_mixture(0, 200, 16, k=4, spread=10.0)
    # intra-cluster distances far below inter-cluster
    intra, inter = [], []
    for i in range(0, 200, 7):
        for j in range(i + 1, 200, 11):
            d = np.linalg.norm(X[i] - X[j])
            (intra if y[i] == y[j] else inter).append(d)
    assert np.mean(intra) < 0.5 * np.mean(inter)


def test_gaussian_mixture_deterministic():
    """Same seed ⇒ bit-identical points AND labels — the quality harness
    diffs approximate tiers against ground truth, so the draw being a
    pure function of the seed is load-bearing."""
    a_pts, a_lab = gaussian_mixture(3, 150, 8, k=5)
    b_pts, b_lab = gaussian_mixture(3, 150, 8, k=5)
    np.testing.assert_array_equal(a_pts, b_pts)
    np.testing.assert_array_equal(a_lab, b_lab)
    c_pts, _ = gaussian_mixture(4, 150, 8, k=5)
    assert not np.array_equal(a_pts, c_pts)


def test_gaussian_mixture_return_labels_flag():
    """return_labels=False returns just the points, from the *identical*
    draw — the two forms describe one dataset."""
    pts_only = gaussian_mixture(3, 150, 8, k=5, return_labels=False)
    pts, labels = gaussian_mixture(3, 150, 8, k=5)
    assert isinstance(pts_only, np.ndarray)
    np.testing.assert_array_equal(pts_only, pts)
    assert labels.shape == (150,)


def test_gaussian_mixture_validates_k():
    with pytest.raises(ValueError, match="1 <= k <= n"):
        gaussian_mixture(0, 10, 4, k=11)
    with pytest.raises(ValueError, match="1 <= k <= n"):
        gaussian_mixture(0, 10, 4, k=0)
    # boundary values are legal
    pts, labels = gaussian_mixture(0, 10, 4, k=10)
    assert pts.shape == (10, 4)
    pts, labels = gaussian_mixture(0, 10, 4, k=1)
    assert np.all(labels == 0)


def test_conformations_rmsd_clusterable():
    from repro.core.distance import pairwise_rmsd

    C, y = conformations(0, 24, 16, k=3, noise=0.05)
    D = np.asarray(pairwise_rmsd(C))
    same = D[y[:, None] == y[None, :]]
    diff = D[y[:, None] != y[None, :]]
    same = same[same > 0]
    assert same.mean() < 0.5 * diff.mean()
