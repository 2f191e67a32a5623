"""Dendrogram post-processing: linkage matrix, cuts, invariants."""

import numpy as np
import pytest

from repro.core import dendrogram as dg
from repro.core.lance_williams import lance_williams
from tests.conftest import random_distance_matrix


def _merges(rng, n=20, method="complete"):
    D = random_distance_matrix(rng, n)
    return np.asarray(lance_williams(D, method=method).merges)


def test_cut_extremes(rng):
    m = _merges(rng)
    n = m.shape[0] + 1
    labels_n = dg.cut(m, n)
    assert sorted(labels_n) == list(range(n))       # every point its own
    labels_1 = dg.cut(m, 1)
    assert (labels_1 == 0).all()                    # one big cluster


def test_cut_counts(rng):
    m = _merges(rng)
    for k in (2, 3, 7):
        labels = dg.cut(m, k)
        assert len(np.unique(labels)) == k


def test_cut_nesting(rng):
    """Cuts are hierarchical: the k-cluster partition refines k-1."""
    m = _merges(rng)
    for k in (2, 4, 8):
        fine = dg.cut(m, k)
        coarse = dg.cut(m, k - 1)
        # every fine cluster maps into exactly one coarse cluster
        for c in np.unique(fine):
            assert len(np.unique(coarse[fine == c])) == 1


def test_monotone_for_reducible(rng):
    for method in ("single", "complete", "average", "ward"):
        D = random_distance_matrix(rng, 24,
                                   squared=method == "ward")
        m = np.asarray(lance_williams(D, method=method).merges)
        assert dg.is_monotone(m), method


def test_linkage_matrix_ids(rng):
    m = _merges(rng, n=10)
    Z = dg.to_linkage_matrix(m)
    n = 10
    seen = set()
    for t in range(n - 1):
        a, b = int(Z[t, 0]), int(Z[t, 1])
        assert a not in seen and b not in seen      # each cluster merged once
        seen.update((a, b))
        assert Z[t, 3] >= 2
    assert Z[-1, 3] == n


def test_validate_merges_catches_corruption(rng):
    m = _merges(rng, n=8)
    bad = m.copy()
    bad[2, 0], bad[2, 1] = bad[1, 0], bad[1, 1]     # merge a dead slot again
    bad[1, 1] = bad[1, 0]
    with pytest.raises(AssertionError):
        dg.validate_merges(bad)


# ---------------------------------------------------------------------------
# canonical ordering + cross-engine equivalence (the NN-chain contract)
# ---------------------------------------------------------------------------


def test_canonical_order_identity_on_sorted(rng):
    """Every LW engine's output is already canonical — a fixed point."""
    m = _merges(rng)
    assert np.array_equal(dg.canonical_order(m), m)


def test_canonical_order_restores_shuffled_independent_merges(rng):
    """Chain-order output (height-shuffled, dependencies respected)
    canonicalizes back to the height-sorted list."""
    m = _merges(rng, n=16)
    # shuffle only *independent* adjacent pairs (no shared slots) — a
    # conservative stand-in for chain order
    shuffled = m.copy()
    for t in range(0, m.shape[0] - 1, 2):
        if not set(m[t, :2]) & set(m[t + 1, :2]):
            shuffled[[t, t + 1]] = shuffled[[t + 1, t]]
    out = dg.canonical_order(shuffled)
    assert np.array_equal(out, m)
    dg.validate_merges(out)


def test_canonical_order_rejects_dependency_breaking_input(rng):
    """An inversion that would reorder a merge before the merge that
    created its operand must raise, not corrupt the tree."""
    m = _merges(rng, n=8)
    bad = m.copy()
    bad[-1, 2] = -1.0        # parent of everything sorted to the front
    with pytest.raises(AssertionError):
        dg.canonical_order(bad)


def test_merge_leafsets_laminar(rng):
    m = _merges(rng, n=12)
    sets = dg.merge_leafsets(m)
    assert len(set(sets)) == len(sets)               # all distinct
    assert sets[-1] == frozenset(range(12))          # root holds everything
    for a in sets:
        for b in sets:
            assert a <= b or b <= a or not (a & b)   # laminar family


def test_merges_equivalent_detects_structure_and_heights(rng):
    m = _merges(rng, n=14)
    assert dg.merges_equivalent(m, m)
    # reordered independent merges: still the same dendrogram
    shuffled = m.copy()
    if not set(m[0, :2]) & set(m[1, :2]):
        shuffled[[0, 1]] = shuffled[[1, 0]]
    assert dg.merges_equivalent(m, shuffled)
    # a height perturbation beyond tolerance is NOT equivalent
    bumped = m.copy()
    bumped[3, 2] += 1.0
    assert not dg.merges_equivalent(m, bumped)
    # a truncated list is not equivalent (shape mismatch)
    assert not dg.merges_equivalent(m, m[:-1], n=14)


# ---------------------------------------------------------------------------
# the vectorized host passes against the per-merge loops they replaced
# ---------------------------------------------------------------------------
# The three loops below are the per-merge versions of to_linkage_matrix,
# canonical_order's clamp and validate_merges; the library computes the
# same through one slot-writer index, and must agree bit for bit — arrays,
# dtypes and exceptions.


def _loop_to_linkage_matrix(merges, n=None):
    merges = np.asarray(merges)
    n = dg._leaf_count(merges, n)
    slot_id = np.arange(n)          # which cluster-id currently sits in a slot
    Z = np.zeros((merges.shape[0], 4))
    for t in range(merges.shape[0]):
        i, j, dist, size = merges[t]
        i, j = int(round(i)), int(round(j))
        a, b = slot_id[i], slot_id[j]
        Z[t] = (min(a, b), max(a, b), dist, size)
        slot_id[i] = n + t
    return Z


def _loop_canonical_order(merges, n=None, *, rtol=1e-5, atol=1e-7):
    merges = np.array(merges, copy=True)
    n = dg._leaf_count(merges, n)
    heights = merges[:, 2]
    floor = np.zeros(n, heights.dtype)  # height of the slot's current cluster
    for t in range(merges.shape[0]):
        i, j = int(round(merges[t, 0])), int(round(merges[t, 1]))
        need = max(floor[i], floor[j])
        if heights[t] < need and heights[t] >= need - (atol + rtol * abs(need)):
            heights[t] = need
        floor[i] = heights[t]
    out = merges[np.argsort(heights, kind="stable")]
    _loop_validate_merges(out, n=n)
    return out


def _loop_validate_merges(merges, n=None):
    merges = np.asarray(merges)
    n = dg._leaf_count(merges, n)
    alive = np.ones(n, bool)
    sizes = np.ones(n)
    for t in range(merges.shape[0]):
        i, j = int(round(merges[t, 0])), int(round(merges[t, 1]))
        if not (0 <= i < j < n):
            raise AssertionError(f"step {t}: bad slot pair ({i}, {j})")
        if not (alive[i] and alive[j]):
            raise AssertionError(f"step {t}: merging dead slot ({i}, {j})")
        sizes[i] += sizes[j]
        if abs(sizes[i] - merges[t, 3]) > 1e-3:
            raise AssertionError(
                f"step {t}: recorded size {merges[t, 3]} != {sizes[i]}"
            )
        alive[j] = False
    if n > 1 and merges.shape[0] == n - 1:
        if abs(sizes[int(round(merges[-1, 0]))] - n) > 1e-3:
            raise AssertionError("final cluster does not contain all items")


def _random_tree(n, seed, dtype=np.float32):
    """A random slot-convention tree in emission (chain-like) order: each
    merge sits above the clusters it consumes, but heights are not sorted."""
    rng = np.random.default_rng(seed)
    live, sizes, floor = list(range(n)), np.ones(n), np.zeros(n)
    rows = []
    for _ in range(n - 1):
        a, b = sorted(rng.choice(len(live), 2, replace=False))
        i, j = live[a], live.pop(b)
        sizes[i] += sizes[j]
        floor[i] = max(floor[i], floor[j]) + rng.exponential()
        rows.append((i, j, floor[i], sizes[i]))
    return np.array(rows, dtype).reshape(-1, 4)


def _chain_emission(n=64, seed=3):
    from repro.core.nnchain import nn_chain_from_points

    X = np.random.default_rng(seed).normal(size=(n, 8)).astype(np.float32)
    return np.asarray(nn_chain_from_points(X, "ward").merges)


def _duplicated_points():
    """Raw single-linkage chain output on 4× duplicated quantized points:
    parents one ulp under their children (the clamp's float-noise case)."""
    from repro.core.nnchain import nn_chain

    base = np.round(np.random.default_rng(0).normal(size=(75, 4)) * 2) / 2
    X = np.repeat(base, 4, axis=0).astype(np.float32)
    D = np.sqrt(((X[:, None] - X[None]) ** 2).sum(-1)).astype(np.float32)
    return np.asarray(nn_chain(D, "single").merges)


def _caterpillar(n=40):
    """Leaf ``t + 1`` joins slot 0 at step ``t``, heights falling: with
    ``rtol=1e30`` every merge lifts to the first one's height, a clamp
    chained down the whole depth."""
    t = np.arange(n - 1)
    return np.stack([0 * t, t + 1, n - t, t + 2], axis=1).astype(np.float64)


# name -> (merges, n, canonical_order keywords)
ORACLE_CASES = {
    "random_n2": lambda: (_random_tree(2, 0), None, {}),
    "random_n3": lambda: (_random_tree(3, 1), None, {}),
    "random_n64": lambda: (_random_tree(64, 2, np.float64), None, {}),
    "random_n4096": lambda: (_random_tree(4096, 3), None, {}),
    "nnchain_emission": lambda: (_chain_emission(), None, {}),
    "duplicated_points": lambda: (_duplicated_points(), 300, {}),
    "caterpillar_rtol_1e30": lambda: (_caterpillar(), None, {"rtol": 1e30}),
    "early_stopped": lambda: (_random_tree(50, 4)[:30], 50, {}),
    "n1": lambda: (np.zeros((0, 4), np.float32), None, {}),
}


def _same_array(got, want):
    assert got.dtype == want.dtype and np.array_equal(got, want)


@pytest.mark.parametrize("case", sorted(ORACLE_CASES))
def test_to_linkage_matrix_equals_loop(case):
    merges, n, _ = ORACLE_CASES[case]()
    _same_array(dg.to_linkage_matrix(merges, n=n),
                _loop_to_linkage_matrix(merges, n=n))


@pytest.mark.parametrize("case", sorted(ORACLE_CASES))
def test_canonical_order_equals_loop(case):
    merges, n, kw = ORACLE_CASES[case]()
    _same_array(dg.canonical_order(merges, n=n, **kw),
                _loop_canonical_order(merges, n=n, **kw))


@pytest.mark.parametrize("case", sorted(ORACLE_CASES))
def test_validate_merges_equals_loop(case):
    merges, n, kw = ORACLE_CASES[case]()
    sorted_merges = _loop_canonical_order(merges, n=n, **kw)
    for m in (merges, sorted_merges):
        assert dg.validate_merges(m, n=n) is _loop_validate_merges(m, n=n)


def test_oracle_cases_reach_the_clamp():
    """The duplicated points lift a height within the float budget, and
    the caterpillar lifts every one of its merges."""
    merges, n, _ = ORACLE_CASES["duplicated_points"]()
    lifted = _loop_canonical_order(merges, n=n)
    assert not np.array_equal(np.sort(merges[:, 2]), lifted[:, 2])
    merges, _, kw = ORACLE_CASES["caterpillar_rtol_1e30"]()
    assert (dg.canonical_order(merges, **kw)[:, 2] == merges[0, 2]).all()


def _corrupt(kind):
    """A valid tree broken one way; the step the loop names is in it."""
    m = _random_tree(12, 5)
    if kind == "bad_pair_order":             # i > j
        m[4, [0, 1]] = m[4, [1, 0]]
    elif kind == "bad_pair_range":           # j past the last slot
        m[6, 1] = 12
    elif kind == "dead_slot":                # step 7 reuses step 2's j
        m[7, 1] = m[2, 1]
    elif kind == "size":
        m[5, 3] += 1.0
    elif kind == "final_size":
        # the root records n - 1 leaves; once every step has passed the
        # final-cluster check cannot fire (n - 1 merges of live slots
        # leave one cluster of n), so the last step's size check names it
        m[-1, 3] -= 1.0
    elif kind == "dead_slot_and_size":       # two failures at one step
        m[7, 1] = m[2, 1]
        m[7, 3] += 3.0
    elif kind == "bad_pair_and_size":
        m[4, [0, 1]] = m[4, [1, 0]]
        m[4, 3] += 1.0
    return m


@pytest.mark.parametrize("kind", ["bad_pair_order", "bad_pair_range",
                                  "dead_slot", "size", "final_size",
                                  "dead_slot_and_size", "bad_pair_and_size"])
def test_validate_merges_raises_as_the_loop_does(kind):
    m = _corrupt(kind)
    with pytest.raises(AssertionError) as want:
        _loop_validate_merges(m)
    with pytest.raises(AssertionError) as got:
        dg.validate_merges(m)
    assert str(got.value) == str(want.value)
    assert str(got.value).startswith("step ")


def test_validate_merges_rejects_a_nan_size():
    m = _random_tree(12, 5)
    m[3, 3] = np.nan
    with pytest.raises(AssertionError, match="step 3: recorded size nan"):
        dg.validate_merges(m)
