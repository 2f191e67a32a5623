"""What decides ``correct``: sound runs pass, the controls and the planted
faults fail.

Each fault breaks the timed path underneath the harness, where the answer
is produced, and the rest of a run goes on as on the chip (only the look
for a chip is skipped).  The cells run on one chip, so the fault of an
exchange between chips left out does not apply to them.
"""

from __future__ import annotations

import jax.numpy as jnp
import pytest

from chipbench.control import control_numbers
from chipbench.harness import load_cell
from chipbench.reference import judge

CELLS = ("corpus-ward",)


def _library_fault(kind):
    from repro.core.nnchain import ChainResult

    def patch(orig):
        def chain(X, method="ward", **kw):
            if kind == "half":
                return orig(X[: X.shape[0] // 2], method, **kw)
            res = orig(X, method, **kw)
            if kind == "unchanged":
                return ChainResult(merges=jnp.zeros_like(res.merges),
                                   n_merges=jnp.zeros_like(res.n_merges),
                                   iters=jnp.zeros_like(res.iters))
            return res._replace(merges=res.merges.at[0, 1].add(1.0))
        return chain
    return "repro.core.api.nn_chain_from_points", patch


@pytest.mark.parametrize("cell", CELLS)
def test_sound_runs_are_correct(run_tiny, cell):
    out = run_tiny(cell)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["notes"]["answers_compared"] >= 1
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("kind", ["altered", "half", "unchanged"])
@pytest.mark.parametrize("cell", CELLS)
def test_a_planted_fault_makes_the_run_incorrect(
        run_tiny, checkout, monkeypatch, cell, kind):
    import importlib

    target, patch = _library_fault(kind)
    mod, _, attr = target.rpartition(".")
    owner = importlib.import_module(mod)
    monkeypatch.setattr(owner, attr, patch(getattr(owner, attr)))
    out = run_tiny(cell)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_fails_the_limits(checkout, cell):
    """The control needs no chip; the test reads it at n = 4096."""
    c = load_cell(cell, checkout)
    numbers = control_numbers(c, 2**31 + 11)
    correct, checks = judge(numbers, c.traffic["limits"])
    assert not correct, checks
