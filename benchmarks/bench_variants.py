"""Beyond-paper engine comparison: baseline (paper-faithful full rescan)
vs rowmin (cached row minima) — work per iteration drops from O(n²/p) to
O(n/p) amortized.  Wall-clock on 1 CPU + HLO-derived per-device FLOPs."""

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
import json, time, math
import numpy as np, jax, jax.numpy as jnp
from repro.core.distributed import distributed_lance_williams, make_cluster_mesh, _run
from repro.core.engine import resolve_compaction
from repro.roofline.hlo_cost import HloCost

n, p, variant = {n}, {p}, "{variant}"
compaction = {compaction}
rng = np.random.default_rng(0)
X = rng.normal(size=(n, 8)).astype(np.float32)
D = np.sqrt(((X[:, None] - X[None]) ** 2).sum(-1))
mesh = make_cluster_mesh()
res = distributed_lance_williams(D, "complete", mesh=mesh, variant=variant,
                                 compaction=compaction)
jax.block_until_ready(res.merges)
t0 = time.perf_counter()
res = distributed_lance_williams(D, "complete", mesh=mesh, variant=variant,
                                 compaction=compaction)
jax.block_until_ready(res.merges)
wall = time.perf_counter() - t0

n_pad = math.ceil(n / p) * p
lowered = _run.lower(jax.ShapeDtypeStruct((n_pad, n_pad), jnp.float32),
                     jax.ShapeDtypeStruct((n_pad,), jnp.bool_),
                     jax.ShapeDtypeStruct((n_pad,), jnp.float32),
                     method="complete", n_steps=n - 1, mesh=mesh,
                     variant=variant,
                     compaction=resolve_compaction(compaction, n_pad, n - 1,
                                                   align=p))
cost = HloCost(lowered.compile().as_text(), p).total()
print(json.dumps({{"variant": variant, "wall_s": wall,
                   "flops_per_device": cost.flops,
                   "coll_bytes_per_device": cost.coll_bytes}}))
"""


def run(n: int = 768, p: int = 4, compaction: bool = False):
    rows = []
    print(CPU_ROWS_NOTE)
    for variant in ("baseline", "rowmin", "lazy"):
        out = run_on_cpu_devices(
            _SNIPPET.format(n=n, p=p, variant=variant,
                            compaction=compaction),
            p, timeout=900)
        if out.returncode != 0:
            raise RuntimeError(out.stderr[-2000:])
        rows.append(json.loads(out.stdout.strip().splitlines()[-1]))
    return rows


def main(n: int = 768, p: int = 4, compaction: bool = False):
    rows = run(n, p, compaction=compaction)
    print("name,us_per_call,derived")
    tag = "_compact" if compaction else ""
    for r in rows:
        print(f"lw_dist_{r['variant']}{tag},{r['wall_s'] * 1e6:.0f},"
              f"flops/dev={r['flops_per_device']:.3e};"
              f"coll_B/dev={r['coll_bytes_per_device']:.3e}")
    if rows[0]["wall_s"] > 0:
        for r in rows[1:]:
            print(f"# {r['variant']} vs baseline: "
                  f"{rows[0]['wall_s'] / r['wall_s']:.2f}x wall, "
                  f"{rows[0]['flops_per_device'] / max(r['flops_per_device'],1):.2f}x flops")
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=768)
    ap.add_argument("--p", type=int, default=4)
    ap.add_argument("--compaction", action="store_true",
                    help="run with the engine stage schedule enabled")
    a = ap.parse_args()
    main(n=a.n, p=a.p, compaction=a.compaction)
