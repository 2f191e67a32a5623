"""Paper Figure 2: runtime vs processor count (n ≈ 1968, complete linkage).

Two measurements per processor count p:

* **wall** — actual wall-clock of the distributed engine with p fake CPU
  devices (subprocess).  On this 1-physical-core container the devices
  timeshare, so wall time cannot show speedup — it is recorded for
  completeness and sanity (the paper's cluster had p real CPUs).
* **derived** — per-device compute FLOPs and collective bytes extracted
  from the compiled HLO (loop-aware cost model).  These are exact and
  reproduce the paper's scaling claims: compute/device ∝ 1/p with an
  O(n)-bytes/iteration communication term that grows relatively as p
  rises — the knee of the paper's Figure 2.
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
from repro.core.distributed import distributed_lance_williams, make_cluster_mesh
from repro.roofline.hlo_cost import HloCost

n = {n}
p = {p}
rng = np.random.default_rng(0)
X = rng.normal(size=(n, 8)).astype(np.float32)
D = np.sqrt(((X[:, None] - X[None]) ** 2).sum(-1))
mesh = make_cluster_mesh()
assert mesh.devices.size == p, (mesh.devices.size, p)

# wall time (includes one warm-up for compile)
res = distributed_lance_williams(D, "complete", mesh=mesh)
jax.block_until_ready(res.merges)
t0 = time.perf_counter()
res = distributed_lance_williams(D, "complete", mesh=mesh)
jax.block_until_ready(res.merges)
wall = time.perf_counter() - t0

# derived per-device terms from the compiled HLO
from repro.core.distributed import _run, _pad_matrix
import math, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.distributed import AXIS
n_pad = math.ceil(n / p) * p
Dp = jax.ShapeDtypeStruct((n_pad, n_pad), jnp.float32)
alive = jax.ShapeDtypeStruct((n_pad,), jnp.bool_)
sizes = jax.ShapeDtypeStruct((n_pad,), jnp.float32)
lowered = _run.lower(Dp, alive, sizes, method="complete", n_steps=n - 1,
                     mesh=mesh, variant="baseline")
comp = lowered.compile()
cost = HloCost(comp.as_text(), p).total()
print(json.dumps({{"p": p, "wall_s": wall,
                   "flops_per_device": cost.flops,
                   "coll_bytes_per_device": cost.coll_bytes,
                   "bytes_per_device": cost.bytes}}))
"""


def run(n: int = 1968, procs=(1, 2, 4, 8, 16), timeout: int = 900):
    rows = []
    print(CPU_ROWS_NOTE)
    for p in procs:
        out = run_on_cpu_devices(_SNIPPET.format(n=n, p=p), p,
                                 timeout=timeout)
        if out.returncode != 0:
            raise RuntimeError(f"p={p} failed:\n{out.stderr[-2000:]}")
        rows.append(json.loads(out.stdout.strip().splitlines()[-1]))
    return rows


def main(n: int = 1968, procs=(1, 2, 4, 8, 16)):
    rows = run(n, procs)
    base = rows[0]["flops_per_device"]
    print("p,wall_s,flops_per_device,compute_scaling,coll_bytes_per_device")
    for r in rows:
        print(f"{r['p']},{r['wall_s']:.3f},{r['flops_per_device']:.3e},"
              f"{base / max(r['flops_per_device'], 1):.2f}x,"
              f"{r['coll_bytes_per_device']:.3e}")
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1968)
    ap.add_argument("--procs", type=int, nargs="*", default=[1, 2, 4, 8, 16])
    a = ap.parse_args()
    main(a.n, tuple(a.procs))
