"""CPU fixtures for the benchmark's own tests (``pytest chipbench/tests``).

The tests run the harness on the CPU at tiny sizes, with the look for a
chip skipped, inside a temporary checkout: a copy of ``BENCHMARK.json`` and
``chipbench/`` beside a link to the program's ``src``.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the tests compile for the CPU: keep that out of the persistent cache
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

REPO = Path(__file__).resolve().parents[2]
for p in (REPO, REPO / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

#: Tiny sizes: what each cell's files become in the temporary checkout.
TINY = {
    # the smallest n at which cluster() takes the matrix-free chain
    "configs/sift-corpus.json": {"n_points": 4096},
    "traffic/back-to-back.json": {"pool": 2},
}


def edit_json(path: Path, **changes) -> None:
    doc = json.loads(path.read_text())
    doc.update(changes)
    path.write_text(json.dumps(doc, indent=1))


@pytest.fixture
def checkout(tmp_path: Path) -> Path:
    """A temporary checkout of the benchmark at tiny sizes."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (tmp_path / "src").symlink_to(REPO / "src")
    for rel, changes in TINY.items():
        edit_json(tmp_path / "chipbench" / rel, **changes)
    return tmp_path


@pytest.fixture
def run_tiny(checkout):
    """``run_tiny(cell, seed=..., seconds=...)``: one harness run on the CPU."""
    import time

    from chipbench.harness import run_cell

    def go(cell: str, seed: int = 2**31 + 5, seconds: float = 1.5,
           trace: bool = False) -> dict:
        return run_cell(cell, seed, seconds, trace,
                        t_process=time.perf_counter(), require_tpu=False,
                        root=checkout)

    return go
