"""Paper storage claim: (n²−n)/2 matrix cells split across p units —
each device stores O(n²/p).  Measured from actual addressable shards."""

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
import json
import numpy as np, jax, jax.numpy as jnp, math
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.distributed import make_cluster_mesh, AXIS
n, p = {n}, {p}
mesh = make_cluster_mesh()
n_pad = math.ceil(n / p) * p
D = jnp.zeros((n_pad, n_pad), jnp.float32)
Ds = jax.device_put(D, NamedSharding(mesh, P(AXIS, None)))
per_dev = sorted({{s.device.id: s.data.nbytes for s in Ds.addressable_shards}}.items())
print(json.dumps({{"p": p, "bytes_per_device": per_dev[0][1],
                   "total_bytes": sum(b for _, b in per_dev)}}))
"""


def run(n: int = 1968, procs=(1, 2, 4, 8, 16)):
    """Probe each device count in a subprocess.

    Returns ``(rows, failures)``.  A failing probe surfaces its stderr
    (and unparseable stdout) on *our* stderr and is recorded in
    ``failures`` — the remaining device counts still run, so one broken
    configuration can't silently erase the whole sweep.
    """
    rows, failures = [], []
    print(CPU_ROWS_NOTE)
    for p in procs:
        out = run_on_cpu_devices(_SNIPPET.format(n=n, p=p), p, timeout=300)
        if out.returncode != 0:
            sys.stderr.write(
                f"bench_storage: p={p} probe failed "
                f"(returncode {out.returncode}); stderr tail:\n"
                f"{out.stderr[-2000:]}\n"
            )
            failures.append(p)
            continue
        try:
            rows.append(json.loads(out.stdout.strip().splitlines()[-1]))
        except (ValueError, IndexError):
            sys.stderr.write(
                f"bench_storage: p={p} probe printed no parseable row; "
                f"stdout tail:\n{out.stdout[-500:]}\n"
                f"stderr tail:\n{out.stderr[-2000:]}\n"
            )
            failures.append(p)
    return rows, failures


def main(n: int = 1968, procs=(1, 2, 4, 8, 16)):
    rows, failures = run(n, procs)
    if rows:
        # the reduction baseline is the p=1 probe; if it failed, fall back
        # to the smallest surviving p and say so in the header.  Rows
        # follow the runner's ``name,us_per_call,derived`` convention
        # (``run.py --json``): storage probes have no timing, so the
        # numeric field carries the per-device byte count and the name
        # says so.
        base_row = min(rows, key=lambda r: r["p"])
        base = base_row["bytes_per_device"]
        base_name = ("serial" if base_row["p"] == 1
                     else f"p{base_row['p']}")
        print("name,us_per_call,derived")
        for r in rows:
            print(f"storage_bytes_per_device_p{r['p']},"
                  f"{r['bytes_per_device']},"
                  f"n={n};reduction_vs_{base_name}="
                  f"{base / r['bytes_per_device']:.2f}x")
    if failures:
        raise RuntimeError(
            f"storage probes failed for p in {failures} (stderr above)"
        )
    return rows


if __name__ == "__main__":
    main()
