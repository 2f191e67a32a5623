#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell is looked up in
``BENCHMARK.json``; its configuration, traffic mix and metric readers are
files under ``chipbench/`` (see ``chipbench/README.md``).  The run sets up,
measures for ``--seconds``, checks the window's answers against the plain
reference and prints one JSON object as its last line of standard output:
the cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics
read from a profiler trace of the window.  Without a TPU, or with fewer
chips than the cell asks for, it prints no result and exits with 2.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench.harness import NoChip, print_result, run_cell

    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_process=T_PROCESS)
    except NoChip as exc:
        print(f"not run: {exc}", file=sys.stderr)
        return 2
    print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
