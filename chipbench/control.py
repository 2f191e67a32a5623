#!/usr/bin/env python3
"""Read a cell's control: the reference one precision step lower.

    python3 chipbench/control.py --workload <name> --seeds 1 2 3

For each seed this draws the inputs that a run of the cell checks (the
same seeds give the same inputs), builds each tree with the control named
by the cell's traffic mix (``control``, see ``reference.control_tree``), compares it with the float64 reference the
way a run compares the program's answers, and prints the numbers beside
the cell's limits.  A sound limit sits below every control's reading.
The benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def control_numbers(cell, seed: int) -> dict:
    from chipbench import reference as ref
    from chipbench.harness import Run

    run = Run(cell, seed, 0.0, False, 0.0)
    method, precision = cell.config["method"], cell.traffic["control"]
    tally = ref.Tally()
    for X in cell.driver.check_inputs(run):
        tally.add(ref.control_tree(X, method, precision),
                  ref.reference_tree(X, method))
    return {**tally.numbers(), "answers": tally.answers}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from chipbench.harness import load_cell
    from chipbench.reference import judge

    cell = load_cell(args.workload)
    for seed in args.seeds:
        numbers = control_numbers(cell, seed)
        correct, checks = judge(numbers, cell.traffic["limits"])
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": cell.traffic["control"],
                          "correct": correct, "answers": numbers["answers"],
                          "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
