"""Dendrogram utilities: merge list → tree / labels / linkage matrix.

The engines emit a ``(n-1, 4)`` *merge list* in slot convention —
``(i, j, dist, new_size)`` with ``i < j``, slot ``i`` keeping the union —
which is exactly the paper's "output the current tree level" step.  This
module is the host-side post-processing: conversion to a scipy-style
linkage matrix, flat cluster extraction at any level ``k`` (the paper's
"look k levels down the tree"), and tree invariant checks used by the
property tests.  Pure numpy.

``cluster`` runs :func:`canonical_order` (with :func:`validate_merges`)
and :func:`to_linkage_matrix` on every tree after the device finishes,
so those three are on the host's critical path: each is vectorized over
the merges through one *slot-writer index* (:func:`_slot_writers`),
O(n log n) with no Python loop per merge.
"""

from __future__ import annotations

import numpy as np

from repro.obs import get_registry

# Jacobi rounds of canonical_order's height clamp before it falls back to
# the sequential scan (a clamp chained down a deep tree)
_CLAMP_ROUNDS = 8


def _leaf_count(merges: np.ndarray, n: int | None) -> int:
    """Number of leaves.  ``n`` must be given for early-stopped runs,
    whose merge lists are shorter than ``n - 1``."""
    m = merges.shape[0]
    if n is None:
        return m + 1
    if not m <= n - 1:
        raise ValueError(f"{m} merges is too many for n={n} leaves")
    return n


def _slot_indices(merges: np.ndarray, n: int) -> tuple[np.ndarray, np.ndarray]:
    """Slots ``i`` and ``j`` of every merge as int64 indices into ``n``
    slots, with numpy's indexing rules: a negative slot counts from the
    end, and the first slot (in merge order, ``i`` before ``j``) that is
    not finite or out of range raises what indexing it would raise."""
    r = np.rint(merges[:, :2].astype(np.float64))
    bad = ~np.isfinite(r) | (r < -n) | (r >= n)
    if bad.any():
        t, k = np.argwhere(bad)[0]
        s = int(round(merges[t, k]))     # ValueError/OverflowError if not finite
        raise IndexError(f"index {s} is out of bounds for axis 0 with size {n}")
    ij = r.astype(np.int64)
    ij[ij < 0] += n
    return ij[:, 0], ij[:, 1]


def _slot_writers(i: np.ndarray, j: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """For each merge ``t``, the last earlier merge that wrote its slot
    ``i`` (resp. ``j``) — a merge writes the slot ``i`` that keeps the
    union — or −1 where the slot still holds its leaf.

    One sort of the ``2m`` packed keys ``(slot, 2t + kind)`` — kind 0
    for the query of ``j``, 1 for ``i``, which is also merge ``t``'s
    write — then a running max over the write positions: the last write
    strictly before a key, if it names the same slot, is the answer.
    O(m log m), no Python loop.  Slots must be in ``[0, n)``.
    """
    m = i.shape[0]
    bits = int(2 * m).bit_length()      # room for 2t + kind
    t2 = 2 * np.arange(m, dtype=np.int64)
    keys = np.sort(np.concatenate([(j << bits) | t2, (i << bits) | (t2 + 1)]))
    slot, low = keys >> bits, keys & ((1 << bits) - 1)
    writes = np.where((low & 1) == 1, np.arange(2 * m), -1)
    # position of the last write strictly before each key (-1: none)
    prev = np.maximum.accumulate(np.concatenate([[-1], writes]))[:-1]
    p = np.maximum(prev, 0)
    found = np.where((prev >= 0) & (slot[p] == slot), low[p] >> 1, -1)
    out = np.empty(2 * m, np.int64)
    out[low] = found                    # back to position 2t + kind
    return out[1::2], out[0::2]


def to_linkage_matrix(merges: np.ndarray, n: int | None = None) -> np.ndarray:
    """Convert slot-convention merges to a scipy-style linkage matrix ``Z``.

    Row ``t`` of ``Z`` is ``(id_a, id_b, dist, size)`` where ids ``< n`` are
    leaves and id ``n + t`` names the cluster created at step ``t``.  For
    an early-stopped run pass the leaf count ``n`` explicitly; ``Z`` then
    has one row per performed merge (a truncated forest).
    """
    merges = np.asarray(merges)
    n = _leaf_count(merges, n)
    Z = np.zeros((merges.shape[0], 4))
    i, j = _slot_indices(merges, n)
    wi, wj = _slot_writers(i, j)
    a = np.where(wi < 0, i, n + wi)     # the cluster id sitting in each slot
    b = np.where(wj < 0, j, n + wj)
    Z[:, 0] = np.minimum(a, b)
    Z[:, 1] = np.maximum(a, b)
    Z[:, 2:] = merges[:, 2:4]
    return Z


def cut(merges: np.ndarray, k: int, n: int | None = None) -> np.ndarray:
    """Flat labels for ``k`` clusters — apply the first ``n-k`` merges.

    Labels are contiguous ints in ``[0, k)`` ordered by first appearance.
    For an early-stopped run pass ``n`` explicitly; ``k`` can then reach
    down only to the stop level ``n - len(merges)``.
    """
    merges = np.asarray(merges)
    n = _leaf_count(merges, n)
    if not 1 <= k <= n:
        raise ValueError(f"k must be in [1, {n}], got {k}")
    if n - k > merges.shape[0]:
        raise ValueError(
            f"cannot cut at k={k}: this run stopped early after "
            f"{merges.shape[0]} merges (k >= {n - merges.shape[0]} required)"
        )
    parent = np.arange(n)

    def find(x: int) -> int:
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for t in range(n - k):
        i, j = int(round(merges[t, 0])), int(round(merges[t, 1]))
        parent[find(j)] = find(i)

    roots = np.array([find(a) for a in range(n)])
    _, labels = np.unique(roots, return_inverse=True)
    # re-index by first appearance for determinism
    order = {}
    out = np.empty(n, np.int64)
    for a, lab in enumerate(labels):
        if lab not in order:
            order[lab] = len(order)
        out[a] = order[lab]
    return out


def cut_exemplars(
    merges: np.ndarray, k: int, D: np.ndarray, n: int | None = None
) -> tuple[np.ndarray, np.ndarray]:
    """Cut at ``k`` clusters and pick one *exemplar* (medoid) per cluster.

    ``D`` is the ``(n, n)`` distance matrix the tree was built from.
    Returns ``(labels, exemplars)`` where ``exemplars[c]`` is the leaf of
    cluster ``c`` minimizing the summed distance to the cluster's other
    members (ties go to the lowest leaf index).  The exemplars are the
    per-cluster representatives the streaming-assignment service exports
    (:mod:`repro.service.assign`): a new point is labeled by one
    pairwise-distance call against ``k`` exemplars instead of a full
    re-cluster.
    """
    D = np.asarray(D)
    labels = cut(merges, k, n=n)
    if D.shape != (labels.size, labels.size):
        raise ValueError(
            f"distance matrix {D.shape} does not match n={labels.size} leaves"
        )
    exemplars = np.empty(k, np.int64)
    for c in range(k):
        members = np.flatnonzero(labels == c)
        sub = D[np.ix_(members, members)]
        exemplars[c] = members[int(np.argmin(sub.sum(axis=1)))]
    return labels, exemplars


def canonical_order(
    merges: np.ndarray,
    n: int | None = None,
    *,
    rtol: float = 1e-5,
    atol: float = 1e-7,
) -> np.ndarray:
    """Rewrite a merge list into canonical (non-decreasing height) order.

    The NN-chain engine (:mod:`repro.core.nnchain`) emits merges in
    *chain* order; a **stable** sort by height produces exactly the
    sequence the LW loop emits for the same (tie-free) input — same
    slot pairs (a cluster's slot is the minimum leaf index of its
    members in both engines), heights to float tolerance — because for
    reducible methods a child merge never has a greater height than its
    parent, so the stable sort keeps every dependent pair in dependency
    order.

    Reducibility is exact in real arithmetic but only *approximate* in
    float32: duplicated/quantized points can give a parent merge a
    height one ulp **below** its child's (observed: parent 0.99999976
    under child 1.0 on 4× duplicated points), and a naive sort would
    then order the parent first and corrupt the tree.  So heights are
    first **dependency-clamped**: scanning in emission order, a merge
    whose height falls below the clusters it consumes by at most the
    ``rtol``/``atol`` float-noise budget is lifted to that height
    (within the engines' documented height tolerance); a drop *beyond*
    the budget is a genuine inversion (non-reducible input) and is left
    for :func:`validate_merges` to reject after the sort.  Already
    height-sorted input (every LW engine's output) passes through
    unchanged.

    The clamp runs as Jacobi rounds over the slot-writer index until no
    height changes (children precede parents, so the fixed point is the
    sequential scan's, bit for bit); a clamp chained deeper than
    ``_CLAMP_ROUNDS`` falls back to the sequential scan.  The rounds taken
    (``_CLAMP_ROUNDS + 1`` for the fallback) are observed into the
    ``canonical_clamp_rounds`` histogram of the process registry.
    """
    merges = np.array(merges, copy=True)         # input dtype preserved
    n = _leaf_count(merges, n)
    wi, wj = _slot_writers(*_slot_indices(merges, n))
    merges[:, 2], rounds = _clamp_heights(merges[:, 2], wi, wj, rtol, atol)
    get_registry().histogram(
        "canonical_clamp_rounds",
        "rounds of canonical_order's height clamp per call",
    ).observe(rounds)
    order = np.argsort(merges[:, 2], kind="stable")
    out = merges[order]
    validate_merges(out, n=n)
    return out


def _clamp_heights(
    h0: np.ndarray, wi: np.ndarray, wj: np.ndarray, rtol: float, atol: float
) -> tuple[np.ndarray, int]:
    """:func:`canonical_order`'s dependency clamp and the rounds it took.

    A merge's ``need`` is the larger height of the two clusters it
    consumes (0 for a leaf); its height is lifted to ``need`` when it
    falls below by at most ``atol + rtol·|need|``.  Each round recomputes
    every merge from the previous round's heights; a round that changes
    nothing ends it.
    """
    zero = np.zeros(1, h0.dtype)
    h = h0
    with np.errstate(over="ignore", invalid="ignore"):
        for rounds in range(1, _CLAMP_ROUNDS + 1):
            padded = np.concatenate([zero, h])
            a, b = padded[wi + 1], padded[wj + 1]
            need = np.where(b > a, b, a)                # max(a, b), a on ties
            lift = (h0 < need) & (h0 >= need - (atol + rtol * np.abs(need)))
            nxt = np.where(lift, need, h0)
            if nxt.tobytes() == h.tobytes():
                return h, rounds
            h = nxt
    padded = np.concatenate([zero, h0])
    for t, (a, b) in enumerate(zip((wi + 1).tolist(), (wj + 1).tolist())):
        need = max(padded[a], padded[b])
        if padded[t + 1] < need and padded[t + 1] >= need - (atol + rtol * abs(need)):
            padded[t + 1] = need    # float noise, not a real inversion
    return padded[1:], _CLAMP_ROUNDS + 1


def truncate_canonical(
    merges: np.ndarray,
    n: int,
    stop_at_k: int = 1,
    distance_threshold: float | None = None,
) -> np.ndarray:
    """Apply the LW loop's early-stop semantics to a *canonical* (height-
    sorted) full merge list: keep the first ``n − stop_at_k`` rows, then
    drop everything from the first merge above the threshold on.

    This is the post-hoc half of the NN-chain early-stop contract
    (``cluster``'s docstring): the chain engine always runs the full
    O(n²) agglomeration, and every consumer — the single-problem
    ``cluster`` path, the batched scheduler, the service batcher — cuts
    the :func:`canonical_order` output through this one function so the
    prefix matches what the LW loop's genuine early exit records.  The
    row count comes from the same
    :func:`repro.core.engine.resolve_n_steps` the LW loop trips on —
    one source of truth for the prefix contract.
    """
    from repro.core.engine import resolve_n_steps

    merges = np.asarray(merges)[: resolve_n_steps(n, stop_at_k)]
    if distance_threshold is not None:
        above = merges[:, 2] > distance_threshold
        if above.any():
            merges = merges[: int(np.argmax(above))]
    return merges


def merge_leafsets(merges: np.ndarray, n: int | None = None) -> list[frozenset]:
    """Leaf members of the cluster each merge creates, in merge order.

    The clusters of a dendrogram form a laminar family, so each merge's
    leafset is unique — the list doubles as a canonical identity for
    order-insensitive comparison (:func:`merges_equivalent`).
    """
    merges = np.asarray(merges)
    n = _leaf_count(merges, n)
    members: list[set] = [{a} for a in range(n)]
    out: list[frozenset] = []
    for t in range(merges.shape[0]):
        i, j = int(round(merges[t, 0])), int(round(merges[t, 1]))
        members[i] = members[i] | members[j]
        out.append(frozenset(members[i]))
    return out


def merges_equivalent(
    a: np.ndarray,
    b: np.ndarray,
    n: int | None = None,
    *,
    rtol: float = 1e-4,
    atol: float = 1e-5,
) -> bool:
    """True iff two merge lists describe the same dendrogram.

    Order-insensitive: each list is reduced to its set of created
    clusters (leafsets) with attached heights; the lists are equivalent
    when the cluster sets coincide and per-cluster heights agree to
    tolerance.  This is the cross-engine contract the NN-chain goldens
    assert (``tests/test_nnchain.py``, ``benchmarks/bench_nnchain.py``) —
    robust to both merge reordering and float-level height differences.
    """
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape:
        return False
    ha = dict(zip(merge_leafsets(a, n), a[:, 2]))
    hb = dict(zip(merge_leafsets(b, n), b[:, 2]))
    if set(ha) != set(hb):
        return False
    va = np.array([ha[k] for k in sorted(ha, key=sorted)])
    vb = np.array([hb[k] for k in sorted(hb, key=sorted)])
    return bool(np.allclose(va, vb, rtol=rtol, atol=atol))


def merge_set_agreement(
    a: np.ndarray, b: np.ndarray, n: int | None = None
) -> float:
    """Fraction of created clusters two merge lists share, in ``[0, 1]``.

    Each list is reduced to its set of created leafsets
    (:func:`merge_leafsets`, heights ignored); the score is
    ``|A ∩ B| / max(|A|, |B|)`` — 1.0 iff the trees have identical
    structure.  This is the measured quality gate for the approximate
    tiers (:func:`repro.core.distributed.two_phase_from_points`): the
    two-phase dendrogram's agreement with the exact engine's is
    *reported* in ``benchmarks/bench_distributed.py`` / EXPERIMENTS.md
    rather than assumed.  Compare full runs of the same ``n`` — truncated
    prefixes score against whatever the other list built.
    """
    sa = set(merge_leafsets(a, n))
    sb = set(merge_leafsets(b, n))
    denom = max(len(sa), len(sb))
    return len(sa & sb) / denom if denom else 1.0


def _contingency(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Dense ``(ka, kb)`` contingency table of two label vectors."""
    a = np.asarray(a).ravel()
    b = np.asarray(b).ravel()
    if a.shape != b.shape:
        raise ValueError(
            f"label vectors must have equal length, got {a.shape} vs {b.shape}"
        )
    _, ai = np.unique(a, return_inverse=True)
    _, bi = np.unique(b, return_inverse=True)
    ka, kb = int(ai.max(initial=-1)) + 1, int(bi.max(initial=-1)) + 1
    table = np.zeros((ka, kb), np.int64)
    np.add.at(table, (ai, bi), 1)
    return table


def adjusted_rand_index(a: np.ndarray, b: np.ndarray) -> float:
    """Adjusted Rand index between two flat labelings, in ``[-1, 1]``.

    Pair-counting agreement corrected for chance: 1.0 iff the labelings
    induce the same partition (invariant to label permutation), and
    ≈ 0 in expectation for two *independent* random labelings — which
    is exactly why the approximate-tier quality harness reports it
    alongside :func:`label_agreement` (a high raw agreement on a
    lopsided labeling can be chance; a high ARI cannot).  Pure numpy,
    O(n + ka·kb).
    """
    table = _contingency(a, b)
    n = table.sum()
    if n < 2:
        return 1.0

    def comb2(x):
        return x * (x - 1) / 2.0

    sum_ij = comb2(table.astype(np.float64)).sum()
    sum_a = comb2(table.sum(axis=1).astype(np.float64)).sum()
    sum_b = comb2(table.sum(axis=0).astype(np.float64)).sum()
    total = comb2(float(n))
    expected = sum_a * sum_b / total
    max_index = 0.5 * (sum_a + sum_b)
    if max_index == expected:       # both labelings trivial (all one cluster
        return 1.0                  # or all singletons): identical partitions
    return float((sum_ij - expected) / (max_index - expected))


def label_agreement(a: np.ndarray, b: np.ndarray) -> float:
    """Fraction of items whose labels agree under a greedy cluster match.

    Clusters of ``a`` are matched to clusters of ``b`` greedily by
    descending overlap (each cluster used at most once — deterministic:
    ties break on lowest cluster ids); the score is the matched overlap
    mass over ``n``, in ``[0, 1]``.  Invariant to label permutation and
    1.0 iff the partitions are identical.  This is the "did the
    approximate tier put the points in the same clusters" number the
    landmark gate asserts; report :func:`adjusted_rand_index` next to it
    for the chance-corrected view.
    """
    table = _contingency(a, b)
    n = table.sum()
    if n == 0:
        return 1.0
    flat = [
        (-int(table[i, j]), i, j)
        for i in range(table.shape[0])
        for j in range(table.shape[1])
        if table[i, j] > 0
    ]
    flat.sort()
    used_a: set[int] = set()
    used_b: set[int] = set()
    matched = 0
    for neg, i, j in flat:
        if i in used_a or j in used_b:
            continue
        used_a.add(i)
        used_b.add(j)
        matched += -neg
    return matched / float(n)


def cut_label_agreement(
    merges_a: np.ndarray,
    merges_b: np.ndarray,
    k: int,
    n: int | None = None,
) -> float:
    """:func:`label_agreement` between the ``k``-cuts of two dendrograms.

    Cuts both merge lists at ``k`` clusters over the same ``n`` leaves
    and scores the flat partitions.  This is the *measured* quality gate
    of the approximate tiers (landmark, two-phase): the score against
    the exact engine's dendrogram is reported in
    ``benchmarks/bench_landmark.py`` / EXPERIMENTS.md §Perf-10 and
    asserted ≥ its floor in CI — never assumed.  Complements
    :func:`merge_set_agreement` (tree structure) with a
    partition-at-the-cut view, which is what the serving path (labels,
    exemplars, streaming assignment) actually exposes.
    """
    return label_agreement(cut(merges_a, k, n=n), cut(merges_b, k, n=n))


def merge_heights(merges: np.ndarray) -> np.ndarray:
    return np.asarray(merges)[:, 2]


def is_monotone(merges: np.ndarray, atol: float = 1e-5) -> bool:
    """True iff merge heights are non-decreasing.

    Guaranteed for single/complete/average/weighted/ward (reducible
    linkages); centroid/median may legally produce inversions.
    """
    h = merge_heights(merges)
    return bool(np.all(np.diff(h) >= -atol * np.maximum(1.0, np.abs(h[:-1]))))


def validate_merges(merges: np.ndarray, n: int | None = None) -> None:
    """Structural invariants every engine must satisfy (property tests).

    * each step merges two distinct live slots, ``i < j``
    * slot ``j`` never reappears after being tombstoned
    * sizes sum correctly (the final merge of a *full* run has size ``n``)

    The first failing step raises, its checks taken in the order above
    (a NaN recorded size fails).  Each check is one vectorized mask over
    the steps: a step's mask reads the merges before it only through the
    slot-writer index, and those merges passed, so a child's recorded
    size rounds to its true size.
    """
    merges = np.asarray(merges)
    n = _leaf_count(merges, n)
    m = merges.shape[0]
    t = np.arange(m)
    r = np.rint(merges[:, :2].astype(np.float64))
    pair_ok = (0 <= r[:, 0]) & (r[:, 0] < r[:, 1]) & (r[:, 1] < n)
    # past the first bad pair the index reads garbage, never before it
    i, j = np.nan_to_num(r).clip(0, n - 1).astype(np.int64).T
    death = np.full(n, m)             # first step that tombstones a slot
    np.minimum.at(death, j, t)
    dead = (death[i] < t) | (death[j] < t)
    wi, wj = _slot_writers(i, j)
    rounded = np.rint(merges[:, 3].astype(np.float64))
    sizes = (np.where(wi < 0, 1.0, rounded[wi])
             + np.where(wj < 0, 1.0, rounded[wj]))
    size_bad = ~(np.abs(sizes - merges[:, 3]) <= 1e-3)
    fail = ~pair_ok | dead | size_bad
    if fail.any():
        s = int(np.argmax(fail))
        i, j = int(round(merges[s, 0])), int(round(merges[s, 1]))
        if not (0 <= i < j < n):
            raise AssertionError(f"step {s}: bad slot pair ({i}, {j})")
        if dead[s]:
            raise AssertionError(f"step {s}: merging dead slot ({i}, {j})")
        raise AssertionError(
            f"step {s}: recorded size {merges[s, 3]} != {sizes[s]}"
        )
    if n > 1 and m == n - 1:   # full run: one cluster remains
        if abs(sizes[-1] - n) > 1e-3:
            raise AssertionError("final cluster does not contain all items")
