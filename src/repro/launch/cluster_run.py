"""Distributed clustering driver — the paper's workload as a launchable job.

    python -m repro.launch.cluster_run --n 512 --method complete
    python -m repro.launch.cluster_run --mode rmsd --n 256 --atoms 24
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import cluster
from repro.core.distributed import distributed_pairwise, make_cluster_mesh
from repro.data.synthetic import conformations, gaussian_mixture


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=512)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--atoms", type=int, default=24)
    ap.add_argument("--k", type=int, default=8, help="ground-truth clusters")
    ap.add_argument("--method", default="complete")
    ap.add_argument("--mode", choices=("embed", "rmsd"), default="embed")
    ap.add_argument("--backend", default="auto")
    ap.add_argument("--variant", default="baseline",
                    choices=("baseline", "rowmin", "lazy"))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    ndev = len(jax.devices())
    print(f"[cluster] devices={ndev} n={args.n} method={args.method} "
          f"backend={args.backend} variant={args.variant}")

    if args.mode == "rmsd":
        data, truth = conformations(args.seed, args.n, args.atoms, k=args.k)
        mesh = make_cluster_mesh()
        t0 = time.time()
        D = np.asarray(distributed_pairwise(data, kind="rmsd", mesh=mesh))
        t_build = time.time() - t0
        print(f"[cluster] RMSD matrix build: {t_build:.2f}s "
              f"({args.n}×{args.n}, {args.atoms} atoms)")
        t0 = time.time()
        res = cluster(D, method=args.method, backend=args.backend,
                      variant=args.variant)
    else:
        data, truth = gaussian_mixture(args.seed, args.n, args.dim, k=args.k)
        t0 = time.time()
        res = cluster(data, method=args.method, backend=args.backend,
                      variant=args.variant)
    t_cluster = time.time() - t0

    labels = res.labels(args.k)
    # clustering accuracy vs ground truth (purity)
    purity = 0
    for c in range(args.k):
        members = truth[labels == c]
        if len(members):
            purity += np.bincount(members).max()
    purity /= len(truth)
    print(f"[cluster] {res.n_merges} merges in {t_cluster:.2f}s "
          f"(backend={res.backend}); purity@k={args.k}: {purity:.3f}")
    heights = res.heights()
    print(f"[cluster] merge heights: min={heights.min():.3f} "
          f"max={heights.max():.3f}")


if __name__ == "__main__":
    main()
