"""NN-chain engine goldens — merge-set equivalence against the LW loop
(`core/engine.py` via `lance_williams`), matrix-free points mode, API
wiring, and the Pallas row-vs-points kernel.

Cross-engine contract (DESIGN.md §11): on tie-free input the canonical-
ordered chain output has the LW loop's exact ``(i, j, size)`` sequence
with heights equal to float tolerance (XLA fuses the identical
recurrence DAG differently across the two programs).  The property
tests at the bottom need the optional ``hypothesis`` dependency
(matching ``test_distance.py``'s guarded-import pattern).
"""

import numpy as np
import pytest

from repro.core import cluster
from repro.core import dendrogram as dg
from repro.core.distance import pairwise_sq_euclidean
from repro.core.lance_williams import lance_williams
from repro.core.nnchain import (
    NNCHAIN_AUTO_MIN_N,
    POINTS_METHODS,
    REDUCIBLE_METHODS,
    nn_chain,
    nn_chain_from_points,
    resolve_algorithm,
)
from tests.conftest import random_distance_matrix

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def assert_same_tree(got, want, n, rtol=1e-5, atol=1e-6):
    """The cross-engine golden: exact indices/sizes, tolerant heights,
    and the order-insensitive leafset equivalence on top."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    assert np.array_equal(got[:, [0, 1, 3]], want[:, [0, 1, 3]])
    np.testing.assert_allclose(got[:, 2], want[:, 2], rtol=rtol, atol=atol)
    assert dg.merges_equivalent(got, want, n=n)


# ---------------------------------------------------------------------------
# dense engine vs the LW loop
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", REDUCIBLE_METHODS)
@pytest.mark.parametrize("n", [2, 3, 17, 48])
def test_dense_matches_lw_engine(rng, method, n):
    D = random_distance_matrix(rng, n, squared=method == "ward")
    got = np.asarray(nn_chain(D, method).merges)
    want = np.asarray(lance_williams(D, method=method).merges)
    canon = dg.canonical_order(got, n=n)
    assert_same_tree(canon, want, n)


def test_chain_order_is_valid_and_complete(rng):
    """Raw chain output (pre-canonicalization) is itself a valid merge
    list — every slot pair live at its step, sizes consistent."""
    D = random_distance_matrix(rng, 30)
    merges = np.asarray(nn_chain(D, "average").merges)
    assert merges.shape == (29, 4)
    dg.validate_merges(merges, n=30)
    assert dg.is_monotone(dg.canonical_order(merges, n=30))


def test_upper_triangle_input(rng):
    """nn_chain routes through engine.symmetrize like every backend."""
    D = random_distance_matrix(rng, 12)
    got = np.asarray(nn_chain(np.triu(D), "complete").merges)
    want = np.asarray(nn_chain(D, "complete").merges)
    assert np.array_equal(got, want)


def test_tiny_inputs():
    assert np.asarray(nn_chain(np.zeros((1, 1)), "single").merges).shape == (0, 4)
    res = np.asarray(nn_chain(np.array([[0.0, 2.0], [2.0, 0.0]]), "single").merges)
    np.testing.assert_allclose(res, [[0.0, 1.0, 2.0, 2.0]])


def test_rejects_non_reducible_and_bad_input():
    with pytest.raises(ValueError, match="reducible"):
        nn_chain(np.zeros((3, 3)), "centroid")
    with pytest.raises(ValueError, match="unknown linkage"):
        nn_chain(np.zeros((3, 3)), "nope")
    with pytest.raises(ValueError, match="square"):
        nn_chain(np.zeros((3, 4)), "single")


# ---------------------------------------------------------------------------
# matrix-free points mode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", POINTS_METHODS)
@pytest.mark.parametrize("n", [2, 21, 40])
def test_points_mode_matches_dense_on_sq_euclidean(rng, method, n):
    X = rng.normal(size=(n, 6)).astype(np.float32)
    Dsq = np.asarray(pairwise_sq_euclidean(X))
    got = dg.canonical_order(
        np.asarray(nn_chain_from_points(X, method).merges), n=n
    )
    want = np.asarray(lance_williams(Dsq, method=method).merges)
    # summary arithmetic (‖c_A − c_B‖² forms) differs from the recurrence
    # arithmetic by genuine float error, not just fusion — looser rtol
    assert_same_tree(got, want, n, rtol=1e-4, atol=1e-4)


def test_points_mode_rejects_pair_statistic_methods(rng):
    with pytest.raises(ValueError, match="geometric-summary"):
        nn_chain_from_points(rng.normal(size=(8, 3)), "complete")
    with pytest.raises(ValueError, match="points"):
        nn_chain_from_points(rng.normal(size=(8, 3, 2)), "ward")


def test_points_mode_pallas_route_matches_jnp(rng):
    """The tiled Pallas row kernel (interpret mode on CPU) must produce
    the identical tree, padding included."""
    X = rng.normal(size=(37, 5)).astype(np.float32)
    a = np.asarray(nn_chain_from_points(X, "ward").merges)
    b = np.asarray(
        nn_chain_from_points(X, "ward", use_pallas=True, block_n=128).merges
    )
    assert np.array_equal(a[:, [0, 1, 3]], b[:, [0, 1, 3]])
    np.testing.assert_allclose(a[:, 2], b[:, 2], rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# staged matrix-free chain (live summaries packed each time the count halves)
# ---------------------------------------------------------------------------


def _staged_pair(run, *args, d: int, **kw):
    """One run staged at a floor of 16 rows, one run in a single stage."""
    staged = run(*args, stage_min_bytes=4 * d * 16, **kw)
    single = run(*args, stage_min_bytes=2**62, **kw)
    return staged, single


def _assert_identical(staged, single):
    # same merges, same trips; heights bit-identical on the jnp path
    assert np.array_equal(np.asarray(staged.merges), np.asarray(single.merges))
    assert int(staged.n_merges) == int(single.n_merges)
    assert int(staged.iters) == int(single.iters)


def _staged_points(rng, case):
    if case == "ties":     # duplicated quantised points: exact distance ties
        base = np.round(rng.normal(size=(75, 4)) * 2) / 2
        return np.repeat(base, 4, axis=0).astype(np.float32), 299
    n = {"odd": 301, "non_power_of_two": 600, "partial": 600}[case]
    X = rng.normal(size=(n, 8)).astype(np.float32)
    return X, (400 if case == "partial" else n - 1)


@pytest.mark.parametrize("method", POINTS_METHODS)
@pytest.mark.parametrize("case", ["odd", "non_power_of_two", "ties",
                                  "partial"])
def test_staged_points_chain_equals_one_stage(rng, case, method):
    from repro.core.nnchain import _run_points, points_stage_plan

    X, n_steps = _staged_points(rng, case)
    n, d = X.shape
    staged, single = _staged_pair(
        _run_points, X, np.ones((n,), bool), d=d, method=method,
        n_steps=n_steps, use_pallas=False, block_n=512, interpret=False,
    )
    assert len(points_stage_plan(n, d, n_steps,
                                 stage_min_bytes=4 * d * 16)) > 1
    _assert_identical(staged, single)
    assert int(staged.n_merges) == n_steps


@pytest.mark.parametrize("method", POINTS_METHODS)
def test_staged_summary_chain_equals_one_stage(rng, method):
    """Phase 2 of the two-phase tier: slots are whole clusters with
    pre-accumulated sizes and scatter terms."""
    from repro.core.nnchain import _run_summaries, points_stage_plan

    k = 333
    W = rng.normal(size=(k, 6)).astype(np.float32)
    u = (rng.random(k) * (method != "ward")).astype(np.float32)
    sizes = rng.integers(1, 9, size=k).astype(np.float32)
    staged, single = _staged_pair(_run_summaries, W, u, sizes, d=6,
                                  method=method, n_steps=k - 1)
    assert len(points_stage_plan(k, 6, k - 1, stage_min_bytes=4 * 6 * 16)) > 1
    _assert_identical(staged, single)
    assert np.asarray(staged.merges)[-1, 3] == sizes.sum()


def test_staged_pallas_route_stops_where_the_half_breaks_block_alignment(rng):
    """700 points pad to 768 slots, six 128-row blocks: the plan halves
    to 384 and stops there, since 192 is no whole number of blocks."""
    from repro.core.engine import plan_stages
    from repro.core.nnchain import _run_points, points_stage_plan

    n, n_pad, bn = 700, 768, 128
    X = np.zeros((n_pad, 128), np.float32)
    X[:n, :5] = rng.normal(size=(n, 5))
    staged, single = _staged_pair(
        _run_points, X, np.arange(n_pad) < n, d=128, method="ward",
        n_steps=n - 1, use_pallas=True, block_n=bn, interpret=True,
    )
    assert points_stage_plan(n_pad, 128, n - 1, align=bn,
                             stage_min_bytes=4 * 128 * 16) == (
        (768, 384), (384, n - 1 - 384))
    assert len(plan_stages(n_pad, n - 1, min_stage=16)) > 2   # unaligned
    got, want = np.asarray(staged.merges), np.asarray(single.merges)
    assert np.array_equal(got[:, [0, 1, 3]], want[:, [0, 1, 3]])
    np.testing.assert_allclose(got[:, 2], want[:, 2], rtol=1e-6)
    jnp_route = np.asarray(nn_chain_from_points(X[:n, :5], "ward").merges)
    assert np.array_equal(got[:, [0, 1, 3]], jnp_route[:, [0, 1, 3]])


def test_staged_chain_on_nan_input_ends_at_the_global_trip_cap(rng):
    from repro.core.nnchain import _run_points, points_stage_plan

    n = 300
    X = rng.normal(size=(n, 4)).astype(np.float32)
    X[17, 2] = np.nan
    staged, single = _staged_pair(
        _run_points, X, np.ones((n,), bool), d=4, method="ward",
        n_steps=n - 1, use_pallas=False, block_n=512, interpret=False,
    )
    assert len(points_stage_plan(n, 4, n - 1, stage_min_bytes=4 * 4 * 16)) > 1
    assert int(staged.iters) == int(single.iters) == 4 * n + 8
    assert int(staged.n_merges) < n - 1
    with pytest.raises(RuntimeError, match="iteration cap"):
        cluster(X, "ward", matrix_free=True)


@pytest.mark.parametrize("chain_len", [0, 1, 5])
def test_compact_chain_packs_live_slots_in_ascending_order(rng, chain_len):
    """One gather pass at a stage boundary: the live summaries, sizes
    and original ids move to the front in ascending slot order, and the
    chain stack is rewritten to the compacted ids."""
    from repro.core.nnchain import NNState, _compact_chain

    n, half, d = 64, 32, 3
    live_ids = np.sort(rng.choice(n, size=half, replace=False))
    alive = np.zeros(n, bool)
    alive[live_ids] = True
    W = rng.normal(size=(n, d)).astype(np.float32)
    u = rng.random(n).astype(np.float32)
    sizes = np.where(alive, rng.integers(1, 9, size=n), 0).astype(np.float32)
    chain = np.zeros(n, np.int32)
    chain[:chain_len] = rng.choice(live_ids, size=chain_len, replace=False)
    remap = np.arange(100, 100 + n, dtype=np.int32)    # earlier stage's ids
    state = NNState(rep=(W, u), alive=alive, sizes=sizes, chain=chain,
                    chain_len=np.int32(chain_len),
                    merges=np.zeros((n - 1, 4), np.float32),
                    n_merges=np.int32(n - half), iters=np.int32(0))
    out, new_remap = _compact_chain(state, remap, half)
    assert np.asarray(out.alive).all()
    assert np.array_equal(np.asarray(out.rep[0]), W[live_ids])
    assert np.array_equal(np.asarray(out.rep[1]), u[live_ids])
    assert np.array_equal(np.asarray(out.sizes), sizes[live_ids])
    assert np.array_equal(np.asarray(new_remap), remap[live_ids])
    got = np.asarray(out.chain)[:chain_len]
    assert np.array_equal(live_ids[got], chain[:chain_len])


@pytest.mark.parametrize("n,d,stages", [(48, 8, 1), (256, 4096, 2)])
def test_cluster_observes_chain_stages(n, d, stages):
    """One stage below the floor of CHAIN_STAGE_MIN_BYTES; 256 × 4096
    float32 summaries are 4 MiB, so the plan halves once to 128 rows."""
    from repro.core.nnchain import CHAIN_STAGE_MIN_BYTES
    from repro.obs import reset_registry

    assert (n * d * 4 >= 2 * CHAIN_STAGE_MIN_BYTES) == (stages > 1)
    reg = reset_registry()
    X = np.random.default_rng(3).normal(size=(n, d)).astype(np.float32)
    res = cluster(X, "ward", matrix_free=True, keep_inputs=False)
    assert res.chain_stages == stages
    assert reg.get("chain_stages").window() == [stages]
    assert reg.get("chain_trips").window() == [res.chain_trips]


def test_row_kernel_matches_reference(rng):
    from repro.kernels.pairwise import row_sq_euclidean_pallas

    Y = rng.normal(size=(256, 128)).astype(np.float32)
    got = np.asarray(
        row_sq_euclidean_pallas(Y[7], Y, block_n=128, interpret=True)
    )
    want = ((Y - Y[7]) ** 2).sum(-1)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# API wiring (cluster(algorithm=...))
# ---------------------------------------------------------------------------


def test_cluster_nnchain_matches_lw(rng):
    X = rng.normal(size=(50, 5)).astype(np.float32)
    a = cluster(X, "complete", algorithm="nnchain")
    b = cluster(X, "complete", algorithm="lw")
    assert a.algorithm == "nnchain" and b.algorithm == "lw"
    assert_same_tree(a.merges, b.merges, 50)
    assert np.array_equal(a.labels(5), b.labels(5))


def test_cluster_auto_resolution(rng):
    # small n stays on the LW loop
    X = rng.normal(size=(32, 4)).astype(np.float32)
    assert cluster(X, "complete").algorithm == "lw"
    # resolver: large reducible default-knob serial flips to nnchain
    assert resolve_algorithm(
        "auto", method="complete", backend="serial", n=NNCHAIN_AUTO_MIN_N
    ) == "nnchain"
    # pinned LW execution knobs / non-reducible methods / other backends stay
    for kw in (
        dict(method="complete", backend="serial", n=4096, variant="lazy"),
        dict(method="complete", backend="serial", n=4096, compaction=True),
        dict(method="centroid", backend="serial", n=4096),
        dict(method="complete", backend="distributed", n=4096),
        dict(method="complete", backend="kernel", n=4096),
        dict(method="complete", backend="serial", n=NNCHAIN_AUTO_MIN_N - 1),
    ):
        assert resolve_algorithm("auto", **kw) == "lw", kw


def test_cluster_nnchain_early_stop_matches_lw(rng):
    """stop_at_k / distance_threshold are post-hoc truncations on the
    nnchain path — result must equal the LW loop's genuine early exit."""
    X = rng.normal(size=(40, 4)).astype(np.float32)
    full = cluster(X, "complete", algorithm="lw")
    s1 = cluster(X, "complete", algorithm="nnchain", stop_at_k=10)
    s2 = cluster(X, "complete", algorithm="lw", stop_at_k=10)
    assert s1.merges.shape == (30, 4)
    assert np.array_equal(s1.merges[:, [0, 1, 3]], s2.merges[:, [0, 1, 3]])
    assert np.array_equal(s1.labels(12), s2.labels(12))
    # threshold placed mid-gap between two heights: exactly-on-a-height
    # thresholds may legitimately differ by one borderline merge across
    # engines (heights agree only to float tolerance — see cluster docs)
    h = np.asarray(full.merges)[:, 2]
    thr = float((h[len(h) // 2] + h[len(h) // 2 + 1]) / 2)
    t1 = cluster(X, "complete", algorithm="nnchain", distance_threshold=thr)
    t2 = cluster(X, "complete", algorithm="lw", distance_threshold=thr)
    assert t1.merges.shape == t2.merges.shape
    assert np.array_equal(t1.merges[:, [0, 1, 3]], t2.merges[:, [0, 1, 3]])
    assert (np.asarray(t1.merges)[:, 2] <= thr).all()
    both = cluster(X, "complete", algorithm="nnchain", stop_at_k=10,
                   distance_threshold=thr)
    assert both.merges.shape[0] == min(30, t1.merges.shape[0])


def test_cluster_matrix_free_result(rng):
    X = rng.normal(size=(45, 4)).astype(np.float32)
    m = cluster(X, "ward", algorithm="nnchain", matrix_free=True)
    assert m.algorithm == "nnchain"
    assert m.distances is None and m.points is not None   # never materialized
    ref = cluster(X, "ward", algorithm="lw")
    assert dg.merges_equivalent(m.merges, ref.merges, n=45)
    assert np.array_equal(m.labels(4), ref.labels(4))
    # exemplars still work (matrix rebuilt host-side on demand)
    assert len(m.exemplars(4)) == 4
    # average/weighted need the explicit sqeuclidean convention
    msq = cluster(X, "average", metric="sqeuclidean", algorithm="nnchain",
                  matrix_free=True)
    refsq = cluster(X, "average", metric="sqeuclidean", algorithm="lw")
    assert dg.merges_equivalent(msq.merges, refsq.merges, n=45)


def test_matrix_free_true_forces_nnchain(rng):
    """matrix_free=True is a contract: small n (below the auto
    threshold) must still run matrix-free, never silently build (n, n);
    combining with algorithm='lw' is a hard error."""
    X = rng.normal(size=(20, 3)).astype(np.float32)
    r = cluster(X, "ward", matrix_free=True)           # algorithm left "auto"
    assert r.algorithm == "nnchain" and r.distances is None
    ref = cluster(X, "ward", algorithm="lw")
    assert dg.merges_equivalent(r.merges, ref.merges, n=20)
    with pytest.raises(ValueError, match="matrix_free"):
        cluster(X, "ward", algorithm="lw", matrix_free=True)


def test_cluster_algorithm_errors(rng):
    X = rng.normal(size=(12, 3)).astype(np.float32)
    with pytest.raises(ValueError, match="reducible"):
        cluster(X, "centroid", algorithm="nnchain")
    # the chain has serial + distributed compositions (DESIGN.md §12)
    # but still no kernel one — that backend keeps the LW loop
    with pytest.raises(ValueError, match="serial and distributed"):
        cluster(X, "complete", algorithm="nnchain", backend="kernel")
    with pytest.raises(ValueError, match="matrix_free"):
        cluster(X, "complete", algorithm="nnchain", matrix_free=True)
    with pytest.raises(ValueError, match="matrix_free"):
        # default euclidean metric — summaries would be inexact
        cluster(X, "average", algorithm="nnchain", matrix_free=True)
    with pytest.raises(ValueError, match="algorithm"):
        cluster(X, "complete", algorithm="fast")


def test_cluster_duplicated_quantized_points_do_not_crash(rng):
    """Regression: 4× duplicated quantized points give float32 heights
    that violate reducibility by one ulp (a parent merge sorting below
    its child) — canonical_order must absorb the float noise, not raise.
    This input shape is exactly the dedup workload the examples ship."""
    base = np.round(rng.normal(size=(75, 4)) * 2) / 2
    X = np.repeat(base, 4, axis=0).astype(np.float32)      # n=300 > auto min
    for method in ("single", "complete", "ward"):
        r = cluster(X, method)                              # default auto path
        assert r.algorithm == "nnchain"
        dg.validate_merges(np.asarray(r.merges), n=300)
        assert dg.is_monotone(np.asarray(r.merges))
        # every duplicate group coalesces at height ~0 in the 75-cut
        labels = r.labels(75)
        assert all(len(set(labels[g * 4:(g + 1) * 4])) == 1 for g in range(75))


def test_cluster_nnchain_on_multi_device_host():
    """Explicit algorithm='nnchain' with the default backend='auto' must
    resolve to the serial backend on a multi-device host (not raise);
    algorithm='auto' keeps LW-on-distributed there."""
    from tests.conftest import run_with_devices

    out = run_with_devices(
        """
import numpy as np
from repro.core import cluster
X = np.random.default_rng(0).normal(size=(24, 4)).astype(np.float32)
r = cluster(X, "ward", algorithm="nnchain")
assert r.algorithm == "nnchain" and r.backend == "serial", (r.algorithm, r.backend)
r2 = cluster(X, "ward")
assert r2.algorithm == "lw" and r2.backend == "distributed", (r2.algorithm, r2.backend)
assert np.array_equal(r.labels(4), r2.labels(4))
print("multi-device nnchain OK")
""",
        n_devices=2,
    )
    assert "multi-device nnchain OK" in out


def test_cluster_nnchain_distance_matrix_input(rng):
    D = random_distance_matrix(rng, 26)
    a = cluster(D, "single", algorithm="nnchain")
    b = cluster(D, "single", algorithm="lw")
    assert a.distances is not None                 # dense path keeps inputs
    assert_same_tree(a.merges, b.merges, 26)


# ---------------------------------------------------------------------------
# property tests (optional hypothesis dependency)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    @st.composite
    def _problem(draw, max_n=28, max_d=6):
        n = draw(st.integers(2, max_n))
        d = draw(st.integers(1, max_d))
        seed = draw(st.integers(0, 2**31 - 1))
        method = draw(st.sampled_from(REDUCIBLE_METHODS))
        rng = np.random.default_rng(seed)
        return rng.normal(size=(n, d)).astype(np.float32), method

    @settings(max_examples=20, deadline=None)
    @given(_problem())
    def test_nnchain_monotone_and_equivalent_property(problem):
        """For every reducible method on random input: canonical chain
        heights are monotone non-decreasing AND the merge set equals the
        LW engine's (the DESIGN.md §11 exactness claim)."""
        X, method = problem
        n = X.shape[0]
        D = ((X[:, None] - X[None]) ** 2).sum(-1)
        if method != "ward":
            D = np.sqrt(D)
        got = dg.canonical_order(np.asarray(nn_chain(D, method).merges), n=n)
        assert dg.is_monotone(got, atol=1e-4)
        want = np.asarray(lance_williams(D, method=method).merges)
        assert dg.merges_equivalent(got, want, n=n, rtol=1e-3, atol=1e-4)

    @settings(max_examples=10, deadline=None)
    @given(_problem(max_n=20, max_d=4))
    def test_points_mode_equivalent_property(problem):
        X, method = problem
        if method not in POINTS_METHODS:
            return
        n = X.shape[0]
        got = dg.canonical_order(
            np.asarray(nn_chain_from_points(X, method).merges), n=n
        )
        want = np.asarray(
            lance_williams(((X[:, None] - X[None]) ** 2).sum(-1),
                           method=method).merges
        )
        assert dg.is_monotone(got, atol=1e-4)
        assert dg.merges_equivalent(got, want, n=n, rtol=1e-3, atol=1e-3)
