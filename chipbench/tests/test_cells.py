"""Discovery of cells by file name, a cell added from files alone, and the
refusal to run without a TPU."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from chipbench.harness import NoChip, load_cell, read_metrics, reader, run_cell
from conftest import REPO, edit_json

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files_and_readers(cell):
    c = load_cell(cell, REPO)
    assert c.driver.__name__.endswith(c.config["kind"])
    assert hasattr(c.driver, "run") and hasattr(c.driver, "check_inputs")
    e2e = {m["name"] for m in c.metrics_e2e}
    assert "setup_s" in e2e and len(e2e) >= 2 and c.metrics_layer
    for m in c.metrics_e2e + c.metrics_layer:
        assert callable(reader(m["name"], REPO))
        assert m.get("moves", m["name"]) in e2e
    assert set(c.traffic["limits"]) == {"bad", "mismatch", "height_p50"}


def test_which_metrics_a_cell_reports():
    """End-to-end metrics: those that list the cell or list none.
    Per-layer metrics: those that list the cell.  A reader that finds
    nothing in a record leaves its metric out of the line."""
    for w in BENCH["workloads"]:
        c = load_cell(w["name"], REPO)
        assert [m["name"] for m in c.metrics_e2e] == [
            m["name"] for m in BENCH["end_to_end"]
            if w["name"] in m.get("workloads", [w["name"]])]
        assert [m["name"] for m in c.metrics_layer] == [
            m["name"] for m in BENCH["per_layer"]
            if w["name"] in m["workloads"]]
    corpus = load_cell("corpus-ward", REPO)
    assert [m["name"] for m in corpus.metrics_e2e] == ["setup_s", "tree_s"]
    library = {"setup_s": 20.5, "tree_durations_s": [3.5, 3.25]}
    assert read_metrics(corpus.metrics_e2e, library, REPO) == {
        "setup_s": {"value": 20.5, "unit": "s"},
        "tree_s": {"value": 3.375, "unit": "s"}}
    # a service cell's record holds request latencies and no trees
    service = {"setup_s": 20.5, "latencies_s": [0.25, 0.5]}
    assert read_metrics(corpus.metrics_e2e, service, REPO) == {
        "setup_s": {"value": 20.5, "unit": "s"}}


def test_a_per_layer_metric_must_list_its_cells(checkout):
    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    del bench["per_layer"][1]["workloads"]
    (checkout / "BENCHMARK.json").write_text(json.dumps(bench))
    name = bench["per_layer"][1]["name"]
    with pytest.raises(ValueError, match=re.escape(repr(name))):
        load_cell("corpus-ward", checkout)


#: Cells added from files alone: a new configuration (``config`` changed
#: from sift-corpus's), a new mix of two point sets, and, for the first
#: case, a new metric with its reader.  ``reports`` is the end-to-end
#: metrics the new cell's line must hold.
NEW_CELLS = {
    # a new end-to-end metric that lists the one cell reporting it
    "new-metric": {
        "config": {}, "chips": 1,
        "end_to_end": [{"name": "trees_done", "unit": "trees",
                        "better": "higher", "bound": 0.05,
                        "source": "host_clock", "workloads": ["mini-ward"]}],
        "readers": {"trees_done": "def read(rec):\n    return len("
                    "rec.get('tree_durations_s') or ()) or None\n"},
        "reports": {"setup_s", "trees_done", "tree_s"}},
    # a one-chip library cell: the existing entries give it its metrics
    "library": {"config": {}, "chips": 1, "end_to_end": [], "readers": {},
                "reports": {"setup_s", "tree_s"}},
    # the sharded matrix-free chain over four devices, through the same
    # driver and the same reference
    "sharded": {
        "config": {"cluster": {"algorithm": "nnchain",
                               "backend": "distributed",
                               "keep_inputs": False}},
        "chips": 4, "end_to_end": [], "readers": {},
        "reports": {"setup_s", "tree_s"}},
}


def run_on_four_cpu_devices(checkout, cell: str) -> dict:
    """One harness run of ``cell`` in a child that sees four CPU devices."""
    code = ("import json, sys, time\n"
            "sys.path.insert(0, '.')\n"
            "from chipbench.harness import run_cell\n"
            f"out = run_cell({cell!r}, {2**31 + 7}, 0.5, False, "
            "t_process=time.perf_counter(), require_tpu=False)\n"
            "print(json.dumps(out))\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    got = subprocess.run([sys.executable, "-c", code], cwd=checkout, env=env,
                         capture_output=True, text=True, timeout=300)
    assert got.returncode == 0, got.stderr[-4000:]
    return json.loads(got.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", sorted(NEW_CELLS))
def test_a_cell_added_from_files_alone(checkout, run_tiny, case):
    """New files and appended BENCHMARK.json entries only: every file and
    every entry that was there is unchanged, and the new cell reports its
    metrics and reads ``correct``."""
    new = NEW_CELLS[case]
    cb = checkout / "chipbench"
    before = {p: p.read_bytes() for p in cb.rglob("*") if p.is_file()}
    bench0 = json.loads((checkout / "BENCHMARK.json").read_text())

    shutil.copy(cb / "configs" / "sift-corpus.json",
                cb / "configs" / "mini-corpus.json")
    edit_json(cb / "configs" / "mini-corpus.json", n_points=64, dim=32,
              **new["config"])
    shutil.copy(cb / "traffic" / "back-to-back.json", cb / "traffic" / "two.json")
    edit_json(cb / "traffic" / "two.json", pool=2)
    for name, text in new["readers"].items():
        (cb / "metrics" / f"{name}.py").write_text(text)
    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "mini-corpus", "source": "https://arxiv.org/abs/1807.05614",
        "file": "chipbench/configs/mini-corpus.json",
        "reduced": ["n_points", "dim"], "why": "a test cell"})
    bench["workloads"].append({
        "name": "mini-ward", "config": "mini-corpus", "traffic": "two",
        "chips": new["chips"], "why": "a test cell"})
    bench["end_to_end"] += new["end_to_end"]
    (checkout / "BENCHMARK.json").write_text(json.dumps(bench))

    if new["chips"] == 1:
        out = run_tiny("mini-ward", seconds=0.5)
    else:
        out = run_on_four_cpu_devices(checkout, "mini-ward")
        assert out["device"]["count"] == 4
    assert out["correct"] and out["attempted"] >= 1
    assert set(out["metrics"]) == new["reports"]
    if "trees_done" in new["reports"]:
        assert out["metrics"]["trees_done"] == {"value": out["attempted"],
                                                "unit": "trees"}

    assert all(p.read_bytes() == b for p, b in before.items())
    after = json.loads((checkout / "BENCHMARK.json").read_text())
    for key, entries in bench0.items():
        if isinstance(entries, list):
            assert after[key][:len(entries)] == entries
        else:
            assert after[key] == entries
    # no per-layer metric reaches the new cell unlisted, and the cells
    # that were there report what they did
    assert load_cell("mini-ward", checkout).metrics_layer == []
    for w in bench0["workloads"]:
        assert ([m["name"] for m in load_cell(w["name"], checkout)
                 .metrics_e2e]
                == [m["name"] for m in load_cell(w["name"], REPO)
                    .metrics_e2e])


def test_refuses_without_a_tpu(checkout):
    import time

    with pytest.raises(NoChip):
        run_cell("corpus-ward", 1, 1.0, False, t_process=time.perf_counter(),
                 root=checkout)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    cmd = [sys.executable, "chipbench/run.py", "--workload", "corpus-ward",
           "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"]
    got = subprocess.run(cmd, cwd=checkout, env=env, capture_output=True,
                         text=True, timeout=300)
    assert got.returncode == 2 and got.stdout.strip() == ""
    assert "not a TPU" in got.stderr
    # a directory that holds only the benchmark's own files runs nothing
    (checkout / "src").unlink()
    got = subprocess.run(cmd, cwd=checkout, env=env, capture_output=True,
                         text=True, timeout=300)
    assert got.returncode != 0 and got.stdout.strip() == ""
