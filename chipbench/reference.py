"""Plain reference trees and the comparison that decides ``correct``.

The reference imports nothing of the program.  It builds the float64
squared-Euclidean distances of the points (a Gram product in blocks, so an
``n = 32768`` corpus takes seconds of BLAS, not minutes of ``pdist``) and
hands their square roots to scipy's ``linkage``.  scipy reports Euclidean
heights for ``ward``/``centroid``/``median``; the program works on squared
distances (the Lance-Williams recurrences), so those heights are squared.

Merges are compared in the program's slot convention ``(i, j, h, size)``
with ``i < j``: a cluster's slot is its lowest leaf.  The numbers of a
comparison (:class:`Tally`):

* ``bad``: answers that never came, came as an error, or are no valid
  tree (a slot merged twice, sizes that do not add up, a wrong count);
* ``mismatch``: the share of merges whose ``(i, j, size)`` is not in the
  reference's multiset of merges (a near-tie resolved the other way
  changes a few, a wrong tree changes many);
* ``height_p50``: the median relative gap between the program's and the
  reference's merge heights, both sorted: the rounding of the distances
  themselves.  A near-tie that float32 resolves the other way (two
  candidate heights closer than its resolution) reshapes one subtree and
  shifts every later height a little, so the mean and the largest gap
  swing with it; the median does not;
* ``height_max``: the largest of those gaps, reported and not judged.

The controls (:func:`control_tree`) are the reference computed in a lower
precision than the configuration states, to show the comparison fails
them.
"""

from __future__ import annotations

import math
from collections import Counter
from dataclasses import dataclass, field

import ml_dtypes
import numpy as np

SQUARED_HEIGHTS = ("ward", "centroid", "median")


def condensed_sq_euclidean(X: np.ndarray, block: int = 2048) -> np.ndarray:
    """Squared Euclidean distances of the rows of ``X`` in float64, in
    scipy's condensed (upper triangle, row-major) order."""
    X = np.asarray(X, np.float64)
    n = X.shape[0]
    sq = np.einsum("ij,ij->i", X, X)
    out = np.empty(n * (n - 1) // 2)
    pos = 0
    for a in range(0, n, block):
        b = min(n, a + block)
        D = sq[a:b, None] + sq[None, a:] - 2.0 * (X[a:b] @ X[a:].T)
        np.maximum(D, 0.0, out=D)
        for r in range(b - a):
            seg = D[r, r + 1:]
            out[pos:pos + seg.size] = seg
            pos += seg.size
    return out


def slot_merges(Z: np.ndarray, squared: bool) -> np.ndarray:
    """scipy's linkage matrix as slot-convention merges ``(i, j, h, size)``."""
    n = Z.shape[0] + 1
    slot = list(range(n)) + [0] * (n - 1)
    out = np.empty((n - 1, 4))
    for t, (a, b, h, size) in enumerate(Z.tolist()):
        i, j = sorted((slot[int(a)], slot[int(b)]))
        slot[n + t] = i
        out[t] = (i, j, h * h if squared else h, size)
    return out


def tree_from_sq(d2: np.ndarray, method: str) -> np.ndarray:
    """Slot-convention merges of the tree over condensed squared distances
    (float64; overwritten with their square roots, to spare memory)."""
    from scipy.cluster.hierarchy import linkage

    Z = linkage(np.sqrt(d2, out=d2), method)
    return slot_merges(Z, method in SQUARED_HEIGHTS)


def reference_tree(X: np.ndarray, method: str) -> np.ndarray:
    """The float64 reference tree of points ``X``."""
    return tree_from_sq(condensed_sq_euclidean(X), method)


def _bf16(a: np.ndarray) -> np.ndarray:
    return np.asarray(a).astype(ml_dtypes.bfloat16).astype(np.float32)


def control_tree(X: np.ndarray, method: str, precision: str) -> np.ndarray:
    """The reference computed one precision step below the configuration.

    ``bfloat16``: points and distances held in bfloat16 (the step below a
    plain float32 path); the tree is then built as the reference builds it.
    """
    if precision == "bfloat16":
        d2 = condensed_sq_euclidean(_bf16(X))
        step = 1 << 24  # round in place, in chunks, to spare memory
        for a in range(0, d2.size, step):
            d2[a:a + step] = _bf16(d2[a:a + step].astype(np.float32))
        return tree_from_sq(d2, method)
    raise ValueError(f"no control for precision {precision!r}")


def valid_tree(merges, n: int) -> bool:
    """``merges`` is a full agglomeration of ``n`` leaves in slot form."""
    m = np.asarray(merges, np.float64)
    if m.shape != (n - 1, 4) or not np.isfinite(m).all():
        return False
    ij = m[:, :2]
    if not np.array_equal(ij, np.round(ij)):
        return False
    size = [1.0] * n
    alive = [True] * n
    for i, j, _, s in m.tolist():
        i, j = int(i), int(j)
        if not (0 <= i < j < n and alive[i] and alive[j]
                and s == size[i] + size[j]):
            return False
        size[i] = s
        alive[j] = False
    return True


@dataclass
class Tally:
    """The numbers compared over the answers a run checks."""

    answers: int = 0
    bad: int = 0
    merges: int = 0
    mismatched: int = 0
    gaps: list = field(default_factory=list)

    @property
    def mismatch(self) -> float:
        return self.mismatched / self.merges if self.merges else 0.0

    def add_missing(self) -> None:
        self.answers += 1
        self.bad += 1

    def add(self, merges, ref: np.ndarray) -> None:
        """Compare one answer with its reference tree."""
        self.answers += 1
        n = ref.shape[0] + 1
        if not valid_tree(merges, n):
            self.bad += 1
            return
        got = np.asarray(merges, np.float64)
        key = lambda m: Counter(map(tuple, m[:, [0, 1, 3]].astype(np.int64).tolist()))  # noqa: E731
        self.mismatched += sum((key(got) - key(ref)).values())
        self.merges += n - 1
        hg, hr = np.sort(got[:, 2]), np.sort(ref[:, 2])
        floor = max(1e-6 * float(np.median(np.abs(hr))), 1e-30)
        self.gaps.append(np.abs(hg - hr) / np.maximum(np.abs(hr), floor))

    def numbers(self) -> dict[str, float]:
        gaps = np.concatenate(self.gaps) if self.gaps else np.zeros(1)
        return {"bad": self.bad, "mismatch": self.mismatch,
                "height_p50": float(np.median(gaps)),
                "height_max": float(gaps.max())}


def judge(numbers: dict[str, float], limits: dict[str, float]
          ) -> tuple[bool, dict[str, dict[str, float]]]:
    """Each number beside its limit; correct when none exceeds its limit."""
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    ok = all(
        not math.isnan(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values()
    )
    return ok, checks
