"""What the program under test recorded about the window's trees.

``cluster`` observes each of its phases into the histogram
``cluster_phase_seconds{phase=...}`` and each chain call's trips into
``chain_trips``, both on the process-global registry of ``repro.obs``.
The harness calls nothing in ``repro`` after the window, so the window's
``k`` completed trees are the last ``k`` observations of each series,
and the warm-up tree is one more before them.
"""

from __future__ import annotations


def window_values(rec: dict, name: str, **labels) -> list[float] | None:
    """The last ``k`` observations of histogram ``name`` (one label set),
    ``k`` the trees the window completed.

    ``None`` where they cannot be told apart from other calls: a tree
    failed, no tree completed, or the series holds fewer than ``k + 1``
    observations (the program records no such series, or the warm-up
    tree is missing).
    """
    k = len(rec.get("tree_durations_s") or ())
    if k == 0 or rec.get("failed", 0) > 0:
        return None
    from repro.obs import get_registry

    hist = get_registry().get(name)
    if hist is None:
        return None
    values = hist.window(**labels)
    return values[-k:] if len(values) >= k + 1 else None
