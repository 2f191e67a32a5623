"""The readers of what the program records about the window's trees:
``api_host_s.lib``, ``chain_trip_us.lib`` and ``chain_trips.lib``."""

from __future__ import annotations

import numpy as np
import pytest

from chipbench.harness import load_cell, reader
from conftest import REPO

NAMES = ("api_host_s.lib", "chain_trip_us.lib", "chain_trips.lib")


def _trees(count: int, n: int = 40) -> list:
    """A warm-up tree and ``count`` window trees through ``cluster``, on a
    fresh process-global registry; the window's results."""
    from repro.core import cluster
    from repro.obs import reset_registry

    reset_registry()
    rng = np.random.default_rng(7)
    sets = [rng.normal(size=(n, 8)).astype(np.float32)
            for _ in range(count + 1)]
    return [cluster(X, "ward", matrix_free=True) for X in sets][1:]


def _read(name: str, rec: dict):
    return reader(name, REPO)(rec)


def test_the_cell_reports_the_program_metrics():
    layer = {m["name"]: m for m in load_cell("corpus-ward", REPO).metrics_layer}
    assert set(NAMES) <= set(layer)
    assert {layer[m]["moves"] for m in NAMES} == {"tree_s"}


def test_readers_take_the_means_over_the_window_trees():
    from repro.obs import get_registry

    results = _trees(3)
    rec = {"tree_durations_s": [1.0] * 3, "failed": 0}
    hist = get_registry().get("cluster_phase_seconds")
    whole = hist.window(phase="cluster")[-3:]
    engine = hist.window(phase="cluster/engine")[-3:]
    trips = [r.chain_trips for r in results]
    assert _read("chain_trips.lib", rec) == pytest.approx(sum(trips) / 3)
    assert _read("chain_trip_us.lib", rec) == pytest.approx(
        sum(engine) / sum(trips) * 1e6)
    host = _read("api_host_s.lib", rec)
    assert host == pytest.approx((sum(whole) - sum(engine)) / 3)
    assert 0 < host < max(whole)


def test_readers_leave_out_the_warm_up_tree():
    _trees(2)
    one = {"tree_durations_s": [1.0], "failed": 0}
    from repro.obs import get_registry

    last = get_registry().get("chain_trips").window()[-1]
    assert _read("chain_trips.lib", one) == last


@pytest.mark.parametrize("rec", [
    {"tree_durations_s": [1.0, 1.0], "failed": 1},   # a tree failed
    {"tree_durations_s": [1.0] * 3, "failed": 0},    # no warm-up observation
    {"tree_durations_s": [], "failed": 0},           # no tree completed
])
def test_readers_give_none_where_the_trees_cannot_be_told_apart(rec):
    _trees(2)
    assert [_read(name, rec) for name in NAMES] == [None] * 3


def test_readers_give_none_where_the_program_records_nothing():
    from repro.obs import reset_registry

    reset_registry()
    rec = {"tree_durations_s": [1.0, 1.0], "failed": 0}
    assert [_read(name, rec) for name in NAMES] == [None] * 3
