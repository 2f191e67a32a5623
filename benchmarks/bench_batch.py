"""Batched multi-problem throughput: ``cluster_batch`` vs Python loops.

The serving scenario from DESIGN.md §9 / EXPERIMENTS.md §Batch: many
independent small problems (B=64, n=128 by default) on the production
mesh (2 fake CPU devices here, matching the container's cores — the
bench runs in a subprocess so the device count doesn't leak into the
caller's jax).

Baselines, all clustering the same 64 problems:

* ``loop_auto``   — the pre-batching way: Python loop over the public
  ``cluster(...)`` with its default ``backend='auto'``, which on a
  multi-device mesh runs every single small problem through the paper's
  *intra*-problem distributed engine (collectives every merge step —
  exactly the mismatch the batched engine removes).
* ``loop_serial`` — Python loop over ``cluster(..., backend='serial')``
  (one problem per dispatch on one device; the other device idles).
* ``loop_numpy``  — Python loop over the pure-numpy oracle ``naive_lw``.

Engines:

* ``batch_serial`` — ``cluster_batch(..., backend='serial')`` (vmap).
* ``batch_auto``   — ``cluster_batch(...)`` → problems sharded across the
  mesh (*inter*-problem parallelism, zero collectives).

The headline ratio is ``batch_auto`` vs ``loop_auto``: same hardware,
same default-policy API, old way vs new way.
"""

from __future__ import annotations

import json
import os
import sys

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src")
if SRC not in sys.path:          # standalone `python benchmarks/...` use
    sys.path.insert(0, SRC)

from repro.fake_devices import CPU_ROWS_NOTE, run_on_cpu_devices  # noqa: E402

_SNIPPET = r"""
import json, time
import numpy as np, jax
from repro.core import cluster, cluster_batch
from repro.core.naive import naive_lw

B, n = {B}, {n}
rng = np.random.default_rng(0)
X = rng.normal(size=(B, n, 8))
mats = [np.sqrt(((x[:, None] - x[None]) ** 2).sum(-1)).astype(np.float32)
        for x in X]

def timed(fn, reps=2):
    fn()                                    # warm-up / compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    return (time.perf_counter() - t0) / reps

# algorithm="lw" pinned on every loop baseline: the batched engines ARE
# the LW loop, so the speedup ratios must compare against the LW Python
# loop — at --paper sizes algorithm="auto" would hand the baselines the
# faster nnchain engine and deflate every headline
t = dict(
    loop_auto=timed(lambda: [cluster(m, "complete", algorithm="lw")
                             for m in mats]),
    loop_serial=timed(
        lambda: [cluster(m, "complete", backend="serial", algorithm="lw")
                 for m in mats]),
    loop_numpy=timed(lambda: [naive_lw(m, method="complete") for m in mats],
                     reps=1),
    batch_serial=timed(lambda: cluster_batch(mats, "complete",
                                             backend="serial")),
    batch_auto=timed(lambda: cluster_batch(mats, "complete")),
)

if {compaction}:
    # stage-schedule sweep: one bucket-wide gather per boundary (lanes
    # merge in lockstep) — on-rows verified bit-identical to off-rows
    off = cluster_batch(mats, "complete", backend="serial", compaction=False)
    on = cluster_batch(mats, "complete", backend="serial", compaction=True)
    assert all(np.array_equal(a.merges, b.merges) for a, b in zip(on, off))
    t["compact_off"] = timed(lambda: cluster_batch(
        mats, "complete", backend="serial", compaction=False))
    t["compact_on"] = timed(lambda: cluster_batch(
        mats, "complete", backend="serial", compaction=True))

# sanity: batched output == looped output on this exact workload
want = [np.asarray(cluster(m, "complete", backend="serial",
                        algorithm="lw").merges)
        for m in mats]
got = cluster_batch(mats, "complete")
assert all(np.array_equal(g.merges, w) for g, w in zip(got, want))

print(json.dumps({{"B": B, "n": n, "devices": len(jax.devices()),
                   "times_s": t}}))
"""


def run(B: int = 64, n: int = 128, devices: int = 2, timeout: int = 900,
        compaction: bool = False) -> dict:
    print(CPU_ROWS_NOTE)
    out = run_on_cpu_devices(
        _SNIPPET.format(B=B, n=n, compaction=compaction), devices,
        timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(f"bench_batch failed:\n{out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def main(B: int = 64, n: int = 128, devices: int = 2,
         compaction: bool = False):
    r = run(B=B, n=n, devices=devices, compaction=compaction)
    t = r["times_s"]
    base = t["loop_auto"]
    print("name,us_per_call,derived")
    for name, sec in t.items():
        pps = r["B"] / sec
        print(f"batch_{name},{sec * 1e6:.0f},"
              f"{pps:.0f}_problems_per_s;{base / sec:.2f}x_vs_loop_auto")
    speedup = base / t["batch_auto"]
    print(f"batch_headline,{t['batch_auto'] * 1e6:.0f},"
          f"B={r['B']};n={r['n']};p={r['devices']};{speedup:.2f}x")
    if compaction:
        ratio = t["compact_off"] / t["compact_on"]
        print(f"batch_compact_headline,{t['compact_on'] * 1e6:.0f},"
              f"{ratio:.2f}x_vs_single_stage;outputs_verified")
    assert speedup >= 5.0, (
        f"batched engine must beat the auto-backend Python loop by >=5x, "
        f"got {speedup:.2f}x")
    return True


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--B", type=int, default=64)
    ap.add_argument("--n", type=int, default=128)
    ap.add_argument("--devices", type=int, default=2)
    ap.add_argument("--compaction", action="store_true",
                    help="add the stage-schedule off/on sweep rows")
    a = ap.parse_args()
    main(a.B, a.n, a.devices, compaction=a.compaction)
