"""The four-chip cell ``corpus-ward-4chip``: the metrics it reports, the
readers of the sharded chain's span and shard count, and one run of the
cell on four CPU devices."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from chipbench.harness import Run, load_cell, reader
from conftest import REPO, edit_json

CELL = "corpus-ward-4chip"
NAMES = ("shard_trip_us.lib", "shard_trips.lib")


def _read(name: str, rec: dict):
    return reader(name, REPO)(rec)


def _record(loop=(0.5, 2.0, 3.0), trips=(100, 300, 500),
            shards=(4, 4, 4)) -> None:
    """A fresh process-global registry holding what ``cluster`` records
    for a warm-up tree and the window's trees on the sharded chain:
    ``sharded_chain/loop`` seconds, chain trips and chain shards."""
    from repro.obs import PHASE_SECONDS, reset_registry

    reg = reset_registry()
    hist = reg.histogram(PHASE_SECONDS)
    for t in loop:
        hist.observe(t, phase="sharded_chain/loop")
    for t in trips:
        reg.histogram("chain_trips").observe(t)
    for s in shards:
        reg.histogram("chain_shards").observe(s)


def test_the_cell_reports_its_own_metrics_only():
    cell = load_cell(CELL, REPO)
    assert cell.workload["chips"] == 4
    assert cell.config["kind"] == "library_short_trace"
    assert cell.config["cluster"] == {"algorithm": "nnchain",
                                      "backend": "distributed",
                                      "keep_inputs": False}
    assert [m["name"] for m in cell.metrics_e2e] == ["setup_s", "tree_s"]
    assert [m["name"] for m in cell.metrics_layer] == list(NAMES)
    assert {m["moves"] for m in cell.metrics_layer} == {"tree_s"}
    corpus = {m["name"] for m in load_cell("corpus-ward", REPO).metrics_layer}
    assert not corpus & set(NAMES)


def test_readers_take_the_means_over_the_window_trees():
    _record()
    rec = {"tree_durations_s": [1.0, 1.0], "failed": 0, "chips": 4}
    assert _read("shard_trip_us.lib", rec) == pytest.approx(5.0 / 800 * 1e6)
    assert _read("shard_trips.lib", rec) == 400
    # the warm-up tree is left out, whatever its shards
    _record(shards=(1, 4, 4))
    assert _read("shard_trips.lib", rec) == 400


@pytest.mark.parametrize("rec, recorded", [
    ({"tree_durations_s": [1.0, 1.0], "failed": 1, "chips": 4}, {}),
    ({"tree_durations_s": [1.0] * 3, "failed": 0, "chips": 4}, {}),
    ({"tree_durations_s": [], "failed": 0, "chips": 4}, {}),
    # a program without the sharded phases and the shard count (the
    # parent of this cell, or a route that is not the sharded chain)
    ({"tree_durations_s": [1.0, 1.0], "failed": 0, "chips": 4},
     {"loop": (), "shards": ()}),
])
def test_readers_give_none_where_the_trees_cannot_be_told_apart(rec,
                                                               recorded):
    _record(**recorded)
    assert [_read(name, rec) for name in NAMES] == [None, None]


@pytest.mark.parametrize("shards, chips", [
    ((4, 4, 1), 4), ((4, 2, 2), 4), ((1, 1, 1), 4),
    ((4, 4, 4), 2), ((4, 4, 4), None),  # a record without the chips
])
def test_shard_trips_needs_every_window_tree_on_every_chip(shards, chips):
    _record(shards=shards)
    rec = {"tree_durations_s": [1.0, 1.0], "failed": 0, "chips": chips}
    assert _read("shard_trips.lib", rec) is None
    assert _read("shard_trip_us.lib", rec) is not None


def test_shard_trips_follows_the_cell_s_chips():
    _record(shards=(2, 2, 2))
    rec = {"tree_durations_s": [1.0, 1.0], "failed": 0, "chips": 2}
    assert _read("shard_trips.lib", rec) == 400


def test_the_cell_runs_on_four_cpu_devices(checkout):
    """One harness run of the cell at a tiny size on four CPU devices:
    ``correct``, its end-to-end metrics, and its per-layer metrics read
    from the program's records of the same window."""
    edit_json(checkout / "chipbench" / "configs" / "sift-corpus-4chip.json",
              n_points=512)
    code = ("import json, sys, time\n"
            "sys.path.insert(0, '.')\n"
            "from chipbench.harness import load_cell, read_metrics, run_cell\n"
            f"out = run_cell({CELL!r}, {2**31 + 13}, 0.5, False, "
            "t_process=time.perf_counter(), require_tpu=False)\n"
            "rec = {'tree_durations_s': [1.0] * out['attempted'],\n"
            "       'failed': out['failed'], 'chips': 4}\n"
            f"out['layer'] = read_metrics(load_cell({CELL!r}).metrics_layer,"
            " rec)\n"
            "from repro.obs import get_registry\n"
            "out['trips'] = get_registry().get('chain_trips').window()\n"
            "print(json.dumps(out))\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    got = subprocess.run([sys.executable, "-c", code], cwd=checkout, env=env,
                         capture_output=True, text=True, timeout=300)
    assert got.returncode == 0, got.stderr[-4000:]
    out = json.loads(got.stdout.strip().splitlines()[-1])
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 1
    assert out["device"]["count"] == 4
    assert set(out["metrics"]) == {"setup_s", "tree_s"}
    assert set(out["layer"]) == set(NAMES)
    window = out["trips"][-out["attempted"]:]
    assert out["layer"]["shard_trips.lib"]["value"] == pytest.approx(
        sum(window) / len(window))
    assert out["layer"]["shard_trip_us.lib"]["value"] > 0


@pytest.mark.parametrize("trace", [False, True])
def test_a_traced_window_lasts_traced_seconds(checkout, trace):
    """An untraced window lasts the run's seconds, as under ``library``;
    a traced one ``TRACED_SECONDS``, and holds the trees started in it."""
    edit_json(checkout / "chipbench" / "configs" / "sift-corpus-4chip.json",
              n_points=64)
    cell = load_cell(CELL, checkout)
    run = Run(cell, 2**31 + 17, 2.0, trace, time.perf_counter())
    try:
        rec = cell.driver.run(run)
    finally:
        if run.trace_dir:
            shutil.rmtree(run.trace_dir, ignore_errors=True)
    window = run.t_window_end - run.t_window
    assert rec["failed"] == 0 and rec["attempted"] >= 1
    assert len(rec["tree_durations_s"]) == rec["attempted"]
    assert rec["chips"] == 4
    if trace:
        assert cell.driver.TRACED_SECONDS <= window < 2.0
        assert rec["notes"]["traced_seconds"] == cell.driver.TRACED_SECONDS
    else:
        assert window >= 2.0
        assert "traced_seconds" not in rec["notes"]
