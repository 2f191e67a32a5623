"""Distance builders — incl. the RMSD (Kabsch) rigid-motion invariance that
the paper's protein pipeline depends on.  The property tests at the
bottom run only when the optional ``hypothesis`` dependency is present
(CI installs it; the deterministic tests above cover the same builders
without it)."""

import numpy as np

from repro.core.distance import (
    kabsch_rmsd,
    pairwise_cosine,
    pairwise_euclidean,
    pairwise_rmsd,
    pairwise_rmsd_cross,
    pairwise_sq_euclidean,
)

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _rand_rot(rng):
    q, _ = np.linalg.qr(rng.normal(size=(3, 3)))
    if np.linalg.det(q) < 0:
        q[:, 0] *= -1
    return q


def test_sq_euclidean_matches_numpy(rng):
    X = rng.normal(size=(40, 7)).astype(np.float32)
    want = ((X[:, None, :] - X[None, :, :]) ** 2).sum(-1)
    np.testing.assert_allclose(np.asarray(pairwise_sq_euclidean(X)), want,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(pairwise_euclidean(X)),
                               np.sqrt(want), rtol=1e-3, atol=1e-3)


def test_cosine_range_and_self(rng):
    X = rng.normal(size=(20, 5)).astype(np.float32)
    D = np.asarray(pairwise_cosine(X))
    assert (D >= -1e-5).all() and (D <= 2 + 1e-5).all()
    np.testing.assert_allclose(np.diag(D), 0.0, atol=1e-5)


def test_rmsd_zero_under_rigid_motion(rng):
    """RMSD(A, R·A + t) == 0 — the Kabsch superposition property."""
    A = rng.normal(size=(17, 3)).astype(np.float32)
    B = A @ _rand_rot(rng).T + rng.normal(size=(1, 3)) * 5
    assert float(kabsch_rmsd(A, B.astype(np.float32))) < 1e-3


def test_rmsd_detects_reflection(rng):
    """Reflections are NOT allowed: mirrored conformation has rmsd > 0."""
    A = rng.normal(size=(17, 3)).astype(np.float32)
    B = A.copy()
    B[:, 0] *= -1
    assert float(kabsch_rmsd(A, B)) > 0.1


def _svd_rmsd(A, B):
    """Float64 Kabsch through numpy's SVD — the reference for the
    Jacobi singular values inside :func:`kabsch_rmsd`."""
    A = A - A.mean(0)
    B = B - B.mean(0)
    U, S, Vt = np.linalg.svd(A.T @ B)
    corr = S[0] + S[1] + np.sign(np.linalg.det(Vt.T @ U.T)) * S[2]
    return np.sqrt(max((np.sum(A * A) + np.sum(B * B) - 2 * corr) / len(A), 0))


def test_rmsd_matches_float64_svd(rng):
    """Jacobi singular values agree with a float64 SVD to the float32
    floor of the RMSD formula — on generic pairs, mirror images and
    planar conformations (a zero singular value)."""
    from repro.data.synthetic import conformations

    confs, _ = conformations(0, 24, 24)
    confs = confs.astype(np.float64)
    planar = confs[0].copy()
    planar[:, 2] = 0.0
    pairs = [(confs[a], confs[b]) for a in range(8) for b in range(8, 24)]
    pairs += [(confs[0], confs[0] * np.array([1.0, 1.0, -1.0])),
              (planar, planar @ _rand_rot(rng).T)]
    for A, B in pairs:
        got = float(kabsch_rmsd(A.astype(np.float32), B.astype(np.float32)))
        assert abs(got - _svd_rmsd(A, B)) < 1e-4


def test_rmsd_scales_with_noise(rng):
    A = rng.normal(size=(30, 3)).astype(np.float32)
    small = A + rng.normal(size=A.shape).astype(np.float32) * 0.01
    big = A + rng.normal(size=A.shape).astype(np.float32) * 0.5
    assert float(kabsch_rmsd(A, small)) < float(kabsch_rmsd(A, big))


def test_pairwise_rmsd_symmetric(rng):
    confs = rng.normal(size=(8, 11, 3)).astype(np.float32)
    D = np.asarray(pairwise_rmsd(confs))
    np.testing.assert_allclose(D, D.T, atol=1e-5)
    np.testing.assert_allclose(np.diag(D), 0.0, atol=1e-4)
    # spot-check one off-diagonal against the pair function
    want = float(kabsch_rmsd(confs[2], confs[5]))
    np.testing.assert_allclose(D[2, 5], want, rtol=1e-3, atol=1e-4)


def test_pairwise_rmsd_cross_matches_pair_function(rng):
    """The assignment path's rectangular RMSD agrees with kabsch per pair."""
    A = rng.normal(size=(4, 9, 3)).astype(np.float32)
    B = rng.normal(size=(3, 9, 3)).astype(np.float32)
    D = np.asarray(pairwise_rmsd_cross(A, B))
    assert D.shape == (4, 3)
    for a in range(4):
        for b in range(3):
            np.testing.assert_allclose(
                D[a, b], float(kabsch_rmsd(A[a], B[b])), rtol=1e-3, atol=1e-4
            )


# ---------------------------------------------------------------------------
# property tests (optional hypothesis dependency)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    @st.composite
    def _points(draw, max_n=24, max_d=8):
        n = draw(st.integers(2, max_n))
        d = draw(st.integers(1, max_d))
        seed = draw(st.integers(0, 2**31 - 1))
        scale = draw(st.sampled_from([1e-3, 1.0, 1e3]))
        rng = np.random.default_rng(seed)
        return (rng.normal(size=(n, d)) * scale).astype(np.float32)

    @st.composite
    def _conformations(draw, max_n=6, max_atoms=12):
        n = draw(st.integers(2, max_n))
        atoms = draw(st.integers(3, max_atoms))
        seed = draw(st.integers(0, 2**31 - 1))
        rng = np.random.default_rng(seed)
        return rng.normal(size=(n, atoms, 3)).astype(np.float32)

    @settings(max_examples=25, deadline=None)
    @given(_points())
    def test_cosine_range_and_clamp_property(X):
        """Cosine distance stays inside [0, 2] for any input scale, and
        the self-diagonal is ~0 (the clamp must not break identity)."""
        D = np.asarray(pairwise_cosine(X))
        assert (D >= 0.0).all() and (D <= 2.0).all()
        np.testing.assert_allclose(np.diag(D), 0.0, atol=1e-5)

    @settings(max_examples=15, deadline=None)
    @given(_conformations())
    def test_rmsd_symmetry_and_zero_diagonal_property(confs):
        D = np.asarray(pairwise_rmsd(confs))
        assert (D >= 0.0).all()
        np.testing.assert_allclose(D, D.T, atol=1e-4)
        np.testing.assert_allclose(np.diag(D), 0.0, atol=1e-3)

    @settings(max_examples=25, deadline=None)
    @given(_points(max_n=16, max_d=6))
    def test_gram_trick_matches_naive_loop_property(X):
        """The MXU-friendly ‖x‖²+‖y‖²−2xyᵀ form agrees with the direct
        per-pair loop (catches catastrophic-cancellation regressions)."""
        got = np.asarray(pairwise_sq_euclidean(X), np.float64)
        n = X.shape[0]
        want = np.zeros((n, n))
        for a in range(n):
            for b in range(n):
                diff = X[a].astype(np.float64) - X[b].astype(np.float64)
                want[a, b] = (diff * diff).sum()
        scale = max(1.0, float(want.max()))
        np.testing.assert_allclose(got / scale, want / scale, atol=1e-5)
