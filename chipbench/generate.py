"""Data generation from the seed.

Everything here is a pure function of ``(seed, parameters)`` and shares no
code with the program under test.  Every seed gets the same amount of
work: the same number of point sets of the same size, with points drawn
from the seed.
"""

from __future__ import annotations

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """Independent generator for one stream of one seed (any int seed)."""
    return np.random.default_rng([int(seed) % 2**64, *stream])


def gaussian_mixture(rng: np.random.Generator, n: int, dim: int, k: int,
                     spread: float = 6.0) -> np.ndarray:
    """``(n, dim)`` float32 points around ``k`` centres of scale ``spread``
    with unit noise (a copy of the library's ``gaussian_mixture``)."""
    if not 1 <= k <= n:
        raise ValueError(f"need 1 <= k <= n components, got k={k}, n={n}")
    centers = rng.normal(scale=spread, size=(k, dim))
    labels = rng.integers(0, k, size=n)
    pts = centers[labels] + rng.normal(size=(n, dim))
    return pts.astype(np.float32)
