"""Discovery of cells by file name, a cell added from files alone, and the
refusal to run without a TPU."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench.harness import NoChip, load_cell, reader, run_cell
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
    corpus = load_cell("corpus-ward", REPO)
    assert [m["name"] for m in corpus.metrics_e2e] == ["setup_s", "tree_s"]
    assert {m["name"] for m in corpus.metrics_layer} == {
        "engine_device_s.lib", "device_idle_share.lib"}


def test_a_cell_added_from_files_alone(checkout, run_tiny):
    """A new configuration, traffic mix and metric: new files and new
    BENCHMARK.json entries only, no existing file edited."""
    cb = checkout / "chipbench"
    before = {p: p.read_bytes() for p in cb.rglob("*") if p.is_file()}
    shutil.copy(cb / "configs" / "sift-corpus.json",
                cb / "configs" / "mini-corpus.json")
    edit_json(cb / "configs" / "mini-corpus.json", n_points=64, dim=32)
    shutil.copy(cb / "traffic" / "back-to-back.json", cb / "traffic" / "two.json")
    edit_json(cb / "traffic" / "two.json", pool=2)
    (cb / "metrics" / "trees_done.py").write_text(
        "def read(rec):\n    return len(rec.get('tree_durations_s') or ())"
        " or None\n")
    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "mini-corpus", "source": "https://arxiv.org/abs/1807.05614",
        "file": "chipbench/configs/mini-corpus.json",
        "reduced": ["n_points", "dim"], "why": "a test cell"})
    bench["workloads"].append({
        "name": "mini-ward", "config": "mini-corpus", "traffic": "two",
        "chips": 1, "why": "a test cell"})
    bench["end_to_end"].append({
        "name": "trees_done", "unit": "trees", "better": "higher",
        "bound": 0.05, "source": "host_clock", "workloads": ["mini-ward"]})
    (checkout / "BENCHMARK.json").write_text(json.dumps(bench))

    out = run_tiny("mini-ward", seconds=0.5)
    assert out["correct"] and out["attempted"] >= 1
    assert set(out["metrics"]) == {"setup_s", "trees_done"}
    assert out["metrics"]["trees_done"] == {"value": out["attempted"],
                                            "unit": "trees"}
    assert all(p.read_bytes() == b for p, b in before.items())
    # the cells that were there do not report the new metric
    assert "trees_done" not in {m["name"] for m in
                                load_cell("corpus-ward", checkout).metrics_e2e}


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
