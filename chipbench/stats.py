"""The arithmetic of the end-to-end metrics, kept with the benchmark."""

from __future__ import annotations

from typing import Sequence


def mean_time(durations: Sequence[float]) -> float:
    """All the time of the completed units over their count."""
    if not durations:
        raise ValueError("no unit completed in the window")
    return sum(durations) / len(durations)
