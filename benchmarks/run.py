# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark runner.

Paper artifact → bench mapping:
  Figure 2 (runtime vs p, n≈1968)     → bench_scaling
  §5.4 storage claim O(n²/p)           → bench_storage
  Table 1 (all linkage methods)        → bench_linkage
  beyond-paper engine (rowmin)         → bench_variants
  unified engine variant×early-stop    → bench_engine
  O(n²) nnchain engine + points mode   → bench_nnchain (EXPERIMENTS §Perf-5)
  sharded matrix-free chain + twophase → bench_distributed (EXPERIMENTS §Perf-7)
  sub-quadratic landmark tier          → bench_landmark (EXPERIMENTS §Perf-10)
  kernel hot-spots                     → bench_kernels
  batched multi-problem engine         → bench_batch (EXPERIMENTS.md §Batch)
  online serving layer (DESIGN.md §10) → bench_service (EXPERIMENTS.md §Service)
  overload sweep + gates (DESIGN.md §14) → bench_service.main_overload
  row-build kernel roofline rows       → roofline_report

Default sizes are CI-scale; pass --paper for the paper-scale n=1968 run.
"""

import argparse
import contextlib
import io
import json
import os
import subprocess
import sys
import traceback

# make `import benchmarks` / `import repro` work when invoked as
# `python benchmarks/run.py` without PYTHONPATH
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "-C", _ROOT, "rev-parse", "HEAD"],
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except Exception:  # noqa: BLE001 — sha is metadata, never fail a bench
        return "unknown"


def _parse_rows(text: str) -> list[dict]:
    """Parse the benches' ``name,us_per_call,derived`` CSV convention."""
    rows = []
    for line in text.splitlines():
        parts = line.strip().split(",", 2)
        if len(parts) != 3 or parts[0] in ("", "name") or parts[0].startswith("#"):
            continue
        if parts[0].endswith("_config"):
            continue        # metadata line: field 2 is a size, not a timing
        try:
            us = float(parts[1])
        except ValueError:
            continue
        rows.append({"name": parts[0], "us_per_call": us, "derived": parts[2]})
    return rows


def _json_path(template: str, suite: str) -> str:
    """Resolve ``--json`` output path for one suite.

    A literal ``<suite>`` placeholder is substituted; otherwise the
    suite name is suffixed before the extension so multi-suite runs
    write one artifact each (``BENCH_engine.json``, ...).
    """
    if "<suite>" in template:
        return template.replace("<suite>", suite)
    root, ext = os.path.splitext(template)
    return f"{root}_{suite}{ext or '.json'}"


def compare_rows(
    fresh: list[dict], baseline: list[dict], tolerance: float = 0.30
) -> tuple[list[str], list[str]]:
    """Diff fresh ``us_per_call`` rows against a committed baseline.

    Returns ``(regressions, notes)``: a row regresses when its fresh
    time exceeds ``baseline × (1 + tolerance)``.  Rows present on only
    one side are notes, not failures (suites grow; a renamed row shows
    up as one `only-in` note on each side).  Speed-ups are notes too —
    a big one usually means the baseline is stale and worth refreshing.
    """
    base = {r["name"]: r["us_per_call"] for r in baseline}
    new = {r["name"]: r["us_per_call"] for r in fresh}
    regressions, notes = [], []
    for name in sorted(base.keys() | new.keys()):
        if name not in new:
            notes.append(f"{name}: only in baseline")
            continue
        if name not in base:
            notes.append(f"{name}: only in fresh run")
            continue
        b, f = base[name], new[name]
        if b <= 0:
            notes.append(f"{name}: baseline is {b} us, cannot compare")
            continue
        ratio = f / b
        if ratio > 1.0 + tolerance:
            regressions.append(
                f"{name}: {f:.1f} us vs baseline {b:.1f} us "
                f"({ratio:.2f}x > {1.0 + tolerance:.2f}x)"
            )
        elif ratio < 1.0 / (1.0 + tolerance):
            notes.append(
                f"{name}: {ratio:.2f}x of baseline — faster; baseline "
                "may be stale"
            )
    return regressions, notes


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--paper", action="store_true",
                    help="paper-scale sizes (n=1968; slower)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-smoke sizes for the suites that support it")
    ap.add_argument("--json", default=None, metavar="BENCH_<suite>.json",
                    help="also write each suite's rows as machine-readable "
                         "JSON (schema: suite, git_sha, rows[{name, "
                         "us_per_call, derived}]) for the CI perf artifact")
    ap.add_argument("--compare", default=None, metavar="BENCH_<suite>.json",
                    help="diff each suite's fresh us_per_call rows against "
                         "this committed --json artifact and exit non-zero "
                         "on regression (the perf gate); <suite> expands as "
                         "for --json, and the baseline is read before the "
                         "suite runs, so the same path may be given to both")
    ap.add_argument("--compare-tolerance", type=float, default=0.30,
                    metavar="FRAC",
                    help="allowed fractional slowdown before a row is a "
                         "regression (default 0.30 = +30%%)")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        bench_batch,
        bench_distributed,
        bench_engine,
        bench_kernels,
        bench_landmark,
        bench_linkage,
        bench_nnchain,
        bench_scaling,
        bench_service,
        bench_storage,
        bench_variants,
        roofline_report,
    )

    n_scale = 1968 if args.paper else 384
    smoke = args.smoke
    jobs = {
        "storage": lambda: bench_storage.main(n=n_scale, procs=(1, 2, 4, 8)),
        "linkage": lambda: bench_linkage.main(n=256 if not args.paper else 512),
        "kernels": lambda: bench_kernels.main(),
        "variants": lambda: bench_variants.main(
            n=384 if not args.paper else 1024, p=4),
        "engine": lambda: bench_engine.main(
            n=512 if not args.paper else 1968, B=32, smoke=smoke),
        "compaction": lambda: bench_engine.main_compaction(
            n=512 if not args.paper else 1968, B=32, smoke=smoke),
        "nnchain": lambda: bench_nnchain.main(n=2048, smoke=smoke),
        "batch": lambda: bench_batch.main(
            B=64 if not smoke else 8, n=128 if not args.paper else 256,
            compaction=True),
        "service": lambda: bench_service.main(
            rate=300.0, duration=3.0 if not args.paper else 10.0,
            smoke=smoke),
        "service_overload": lambda: bench_service.main_overload(smoke=smoke),
        "scaling": lambda: bench_scaling.main(
            n=n_scale, procs=(1, 2, 4, 8) if not args.paper
            else (1, 2, 4, 8, 16)),
        "distributed": lambda: bench_distributed.main(
            smoke=smoke, paper=args.paper),
        "landmark": lambda: bench_landmark.main(smoke=smoke),
        "roofline": roofline_report.main,
    }
    failed = []
    regressed = []
    capture = args.json or args.compare
    sha = _git_sha() if args.json else None
    for name, job in jobs.items():
        if args.only and name != args.only:
            continue
        # load the baseline up front — --json may overwrite the same file
        baseline = None
        if args.compare:
            base_path = _json_path(args.compare, name)
            try:
                with open(base_path) as fh:
                    baseline = json.load(fh)["rows"]
            except (OSError, KeyError, ValueError) as e:
                print(f"bench:{name} compare baseline unreadable "
                      f"({base_path}): {e} — skipping the gate")
        print(f"\n===== bench:{name} =====")
        buf = io.StringIO()
        tee = _Tee(sys.stdout, buf) if capture else sys.stdout
        try:
            with contextlib.redirect_stdout(tee):
                job()
        except Exception as e:  # noqa: BLE001
            failed.append(name)
            traceback.print_exc()
            print(f"bench:{name},FAILED,{type(e).__name__}: {e}")
            continue
        rows = _parse_rows(buf.getvalue()) if capture else []
        if args.json:
            path = _json_path(args.json, name)
            with open(path, "w") as fh:
                json.dump(
                    {"suite": name, "git_sha": sha, "rows": rows},
                    fh, indent=2,
                )
            print(f"bench:{name} rows -> {path}")
        if baseline is not None:
            regs, notes = compare_rows(
                rows, baseline, args.compare_tolerance)
            for line in notes:
                print(f"bench:{name} compare note: {line}")
            for line in regs:
                print(f"bench:{name} REGRESSION: {line}")
            if regs:
                regressed.append(name)
            else:
                print(f"bench:{name} compare: OK "
                      f"(tolerance +{args.compare_tolerance:.0%})")
    if regressed:
        print(f"\nperf gate FAILED: regressions in {', '.join(regressed)}")
    if failed or regressed:
        sys.exit(1)


class _Tee(io.TextIOBase):
    """Mirror bench stdout to the console AND the JSON row parser."""

    def __init__(self, *streams):
        self._streams = streams

    def write(self, s):  # noqa: D102
        for st in self._streams:
            st.write(s)
        return len(s)

    def flush(self):  # noqa: D102
        for st in self._streams:
            st.flush()


if __name__ == '__main__':
    main()
