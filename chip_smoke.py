#!/usr/bin/env python3
"""Smoke run of the clustering library and service on a TPU.

    python chip_smoke.py               # one chip: every single-device phase
    python chip_smoke.py --four-chips  # four chips: the multi-device paths

Each phase drives a public entry point (``cluster``, ``cluster_batch``,
``ClusteringService``, the distributed engines) at a real size, on data
generated from ``--seed``, and checks what comes out against a reference
that does not share the code under test: the numpy LW mirror
(``repro.core.naive``), scipy's ``linkage``, or the one-device result.
Any failed check fails its phase, and any failed phase makes the script
exit non-zero.  The script refuses to run without a TPU: it never falls
back to the CPU.

The timings it prints are single runs (``cold`` includes tracing and
compilation, ``warm`` reuses the compiled program) and the memory figure
is the device's ``peak_bytes_in_use`` so far.  They show that the paths
run; they are not benchmark numbers.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``,
printed only when every phase passed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from dataclasses import dataclass

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))


@dataclass(frozen=True)
class Sizes:
    """Problem sizes of every phase (the defaults are the real sizes)."""

    corpus_n: int = 100_000       # exact corpus tree (ANN-Benchmarks SIFT widths)
    dim: int = 128
    corpus_k: int = 16            # mixture components of the corpus
    exact_n: int = 4096           # same path, checked against scipy
    paper_n: int = 1968           # the paper's RMSD pipeline
    atoms: int = 24
    kernel_n: int = 4096          # Pallas backend vs the serial engine
    dense_buckets: tuple[int, ...] = (8, 16, 32, 64)
    points_buckets: tuple[int, ...] = (64, 128, 256)
    requests: int = 32            # per service
    four_lw_n: int = 4096         # --four-chips: row-sharded LW
    four_chain_n: int = 200_000   # --four-chips: sharded matrix-free chain
    four_batch: int = 32          # --four-chips: problems per cluster_batch


class CheckFailed(AssertionError):
    """A phase's output disagreed with its reference."""


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise CheckFailed(msg)


def peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def timed(fn):
    """``(result, seconds)`` of one call; results are host arrays or
    explicitly blocked, so the clock covers the device work."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def report(phase: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {body} (single run, not a benchmark)", flush=True)


def scipy_merges(X: np.ndarray, method: str) -> np.ndarray:
    """scipy's ``linkage`` as slot-convention merges ``(i, j, h, size)``.

    A cluster's slot is its lowest leaf, as in every engine here.  scipy
    reports ward heights in Euclidean units; the engines work in squared
    Euclidean (the Lance-Williams ward recurrence), so heights are
    squared.
    """
    from scipy.cluster.hierarchy import linkage

    Z = linkage(np.asarray(X, np.float64), method)
    n = Z.shape[0] + 1
    slot = list(range(n)) + [0] * (n - 1)
    out = np.empty((n - 1, 4))
    for t, (a, b, h, size) in enumerate(Z):
        i, j = sorted((slot[int(a)], slot[int(b)]))
        slot[n + t] = i
        out[t] = (i, j, h * h if method == "ward" else h, size)
    return out


def same_slots(got: np.ndarray, want: np.ndarray) -> bool:
    """Index-identical merges: slot pairs and sizes equal, heights to
    float32 tolerance."""
    got, want = np.asarray(got), np.asarray(want)
    return (
        got.shape == want.shape
        and np.array_equal(got[:, [0, 1, 3]], want[:, [0, 1, 3]])
        and np.allclose(got[:, 2], want[:, 2], rtol=1e-5, atol=1e-5)
    )


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------


def phase_corpus(sizes: Sizes, seed: int, ctx: dict) -> None:
    """Exact ward tree over a 10⁵ × 128 corpus: the matrix-free chain."""
    from repro.core import cluster
    from repro.core import dendrogram as dg
    from repro.data.synthetic import gaussian_mixture

    n = sizes.corpus_n
    X, truth = gaussian_mixture(seed, n, sizes.dim, k=sizes.corpus_k)
    res, cold = timed(lambda: cluster(X, "ward", keep_inputs=False))
    again, warm = timed(lambda: cluster(X, "ward", keep_inputs=False))
    require(res.algorithm == "nnchain" and res.distances is None,
            f"ward on {n} points took {res.algorithm!r}, not the "
            "matrix-free chain")
    require(res.merges.shape == (n - 1, 4), f"merges {res.merges.shape}")
    require(bool(np.isfinite(res.merges).all()), "non-finite merges")
    dg.validate_merges(res.merges, n)
    require(dg.is_monotone(res.merges), "ward heights not monotone")
    require(np.array_equal(res.merges, again.merges),
            "two runs on the same points disagree")
    report("corpus", n=n, d=sizes.dim, cold_s=f"{cold:.3f}",
           warm_s=f"{warm:.3f}", peak_bytes=peak_bytes())
    ctx["corpus"] = (X, truth, res)

    # exactness on the same code path, against scipy in float64
    m = sizes.exact_n
    Xs = gaussian_mixture(seed + 1, m, sizes.dim, k=sizes.corpus_k,
                          return_labels=False)
    small, t = timed(lambda: cluster(Xs, "ward", keep_inputs=False))
    require(small.algorithm == "nnchain" and small.distances is None,
            f"ward on {m} points left the matrix-free chain")
    require(dg.merges_equivalent(small.merges, scipy_merges(Xs, "ward"), n=m),
            f"ward chain at n={m} differs from scipy's linkage")
    report("corpus-exact", n=m, cold_s=f"{t:.3f}", reference="scipy")


def phase_paper(sizes: Sizes, seed: int, ctx: dict) -> None:
    """The paper's pipeline: RMSD matrix, then LW and the dense chain."""
    from repro.core import cluster
    from repro.core import dendrogram as dg
    from repro.core.distributed import distributed_pairwise, make_cluster_mesh
    from repro.core.naive import naive_lw
    from repro.data.synthetic import conformations

    n = sizes.paper_n
    confs, _ = conformations(seed, n, sizes.atoms)
    mesh = make_cluster_mesh()
    D, t_rmsd = timed(
        lambda: distributed_pairwise(confs, kind="rmsd", mesh=mesh)
    )
    D = np.asarray(D)
    require(D.shape == (n, n) and bool(np.isfinite(D).all()),
            f"RMSD matrix {D.shape} not finite")
    # one symmetric float32 matrix feeds the engines and the reference
    D = (0.5 * (D + D.T)).astype(np.float32)
    np.fill_diagonal(D, 0.0)

    want = naive_lw(D, "complete")
    lw, cold = timed(lambda: cluster(D, "complete", algorithm="lw",
                                     keep_inputs=False))
    _, warm = timed(lambda: cluster(D, "complete", algorithm="lw",
                                    keep_inputs=False))
    require(lw.algorithm == "lw", lw.algorithm)
    require(same_slots(lw.merges, want),
            "LW complete linkage differs from the numpy reference")
    nn, nn_cold = timed(lambda: cluster(D, "complete", algorithm="nnchain",
                                        keep_inputs=False))
    require(nn.algorithm == "nnchain", nn.algorithm)
    require(dg.merges_equivalent(nn.merges, want, n=n),
            "NN-chain complete linkage differs from the numpy reference")
    report("paper", n=n, atoms=sizes.atoms, rmsd_s=f"{t_rmsd:.3f}",
           lw_cold_s=f"{cold:.3f}", lw_warm_s=f"{warm:.3f}",
           nnchain_cold_s=f"{nn_cold:.3f}", peak_bytes=peak_bytes())


def phase_kernel(sizes: Sizes, seed: int, ctx: dict) -> None:
    """``backend="kernel"``: the Pallas merge loop, compiled for the chip."""
    import jax

    from repro.core import build_distance_matrix, cluster
    from repro.data.synthetic import gaussian_mixture
    from repro.kernels.ops import lance_williams_kernelized

    n = sizes.kernel_n
    X = gaussian_mixture(seed + 2, n, sizes.dim, return_labels=False)
    D = np.asarray(build_distance_matrix(X, "euclidean"))
    kern, cold = timed(lambda: cluster(D, "complete", algorithm="lw",
                                       backend="kernel", keep_inputs=False))
    _, warm = timed(lambda: cluster(D, "complete", algorithm="lw",
                                    backend="kernel", keep_inputs=False))
    serial = cluster(D, "complete", algorithm="lw", backend="serial",
                     keep_inputs=False)
    require(same_slots(kern.merges, serial.merges),
            "kernel backend merges differ from the serial engine")
    text = (
        jax.jit(lambda m: lance_williams_kernelized(m, "complete"))
        .lower(jax.numpy.asarray(D)).compile().as_text()
    )
    require("tpu_custom_call" in text,
            "the kernel backend compiled without a Mosaic kernel")
    report("kernel", n=n, cold_s=f"{cold:.3f}", warm_s=f"{warm:.3f}",
           mosaic_calls=text.count("tpu_custom_call"),
           peak_bytes=peak_bytes())


def _serve(config, requests, reference, equal, phase: str) -> None:
    """Warm a service, submit ``requests``, check each against
    ``reference(x)`` with ``equal`` and the counters against zero."""
    from concurrent.futures import wait

    from repro.service import ClusteringService, engine_jit_cache_size

    with ClusteringService(config) as svc:
        t0 = time.perf_counter()
        warmed = svc.warmup()
        t_warmup = time.perf_counter() - t0
        compiles0 = svc.cache.stats.compiles
        jit0 = engine_jit_cache_size()

        t0 = time.perf_counter()
        futures = [svc.submit(x) for x in requests]
        done, pending = wait(futures, timeout=600)
        t_serve = time.perf_counter() - t0
        require(not pending, f"{len(pending)} requests never resolved")
        results = [f.result() for f in futures]   # raises a caught failure

        compiles = svc.cache.stats.compiles - compiles0
        jits = engine_jit_cache_size() - jit0
        m = svc.metrics
        require(compiles == 0 and jits == 0,
                f"{compiles} AOT and {jits} implicit compiles after warmup")
        require(m.n_failed == 0 and m.n_retries == 0
                and m.n_worker_restarts == 0,
                f"failed={m.n_failed} retries={m.n_retries} "
                f"restarts={m.n_worker_restarts}")
    for x, res in zip(requests, results):
        require(equal(res.merges, reference(x), x.shape[0]),
                f"service result for n={x.shape[0]} differs from "
                "cluster(backend='serial')")
    report(phase, requests=len(requests), warmed=warmed,
           warmup_s=f"{t_warmup:.3f}", serve_s=f"{t_serve:.3f}",
           compiles_after_warmup=compiles, peak_bytes=peak_bytes())


def phase_service(sizes: Sizes, seed: int, ctx: dict) -> None:
    """Two warmed services, ragged traffic, zero compiles after warmup."""
    from repro.core import build_distance_matrix, cluster
    from repro.core import dendrogram as dg
    from repro.service import ServiceConfig

    rng = np.random.default_rng(seed + 3)

    lo, hi = 3, max(sizes.dense_buckets)
    mats = []
    for _ in range(sizes.requests):
        pts = rng.normal(size=(int(rng.integers(lo, hi + 1)), sizes.dim))
        mats.append(np.asarray(build_distance_matrix(pts), np.float32))
    _serve(
        ServiceConfig(method="complete", bucket_ns=sizes.dense_buckets,
                      max_batch=8),
        mats,
        lambda m: cluster(m, "complete", backend="serial").merges,
        lambda got, want, n: np.array_equal(got, want),
        "service-dense",
    )

    lo = min(sizes.points_buckets) // 2 + 1
    hi = max(sizes.points_buckets)
    points = [
        rng.normal(size=(int(rng.integers(lo, hi + 1)), sizes.dim))
        .astype(np.float32)
        for _ in range(sizes.requests)
    ]
    _serve(
        ServiceConfig(method="ward", algorithm="auto", points_dim=sizes.dim,
                      bucket_ns=sizes.points_buckets, max_batch=4),
        points,
        lambda x: cluster(x, "ward", backend="serial").merges,
        lambda got, want, n: dg.merges_equivalent(got, want, n=n),
        "service-points",
    )


def phase_landmark(sizes: Sizes, seed: int, ctx: dict) -> None:
    """Landmark tier on the corpus points, gated against the exact tree."""
    from repro.core import cluster
    from repro.core import dendrogram as dg

    require("corpus" in ctx, "needs the corpus phase's exact tree")
    X, truth, exact = ctx["corpus"]
    n, k = X.shape[0], sizes.corpus_k
    res, cold = timed(lambda: cluster(X, "ward", algorithm="landmark",
                                      keep_inputs=False))
    _, warm = timed(lambda: cluster(X, "ward", algorithm="landmark",
                                    keep_inputs=False))
    require(res.merges.shape == (n - 1, 4), f"merges {res.merges.shape}")
    dg.validate_merges(res.merges, n)
    agree = dg.cut_label_agreement(res.merges, exact.merges, k, n=n)
    ari = dg.adjusted_rand_index(dg.cut(res.merges, k, n=n),
                                 dg.cut(exact.merges, k, n=n))
    require(agree >= 0.95 and ari >= 0.95,
            f"landmark cut agreement {agree:.4f} / ARI {ari:.4f} < 0.95")
    report("landmark", n=n, k=k, cut_agreement=f"{agree:.4f}",
           ari=f"{ari:.4f}",
           ari_truth=f"{dg.adjusted_rand_index(dg.cut(res.merges, k, n=n), truth):.4f}",
           cold_s=f"{cold:.3f}", warm_s=f"{warm:.3f}",
           peak_bytes=peak_bytes())


ONE_CHIP = (phase_corpus, phase_paper, phase_kernel, phase_service,
            phase_landmark)


# ---------------------------------------------------------------------------
# four-chip phases: what exists only across chips
# ---------------------------------------------------------------------------


def _on_own_devices(arr, p: int) -> bool:
    """Every shard of a row-sharded array on a device of its own."""
    devices = {s.device for s in arr.addressable_shards}
    return len(devices) == p == len(arr.addressable_shards)


def phase_four_lw(sizes: Sizes, seed: int, ctx: dict) -> None:
    """The paper's row-sharded LW loop vs the one-device engine."""
    import jax

    from repro.core import cluster
    from repro.core.distributed import (
        distributed_lance_williams,
        distributed_pairwise,
        make_cluster_mesh,
    )
    from repro.data.synthetic import gaussian_mixture

    n = sizes.four_lw_n
    mesh = make_cluster_mesh()
    p = mesh.devices.size
    X = gaussian_mixture(seed + 4, n, sizes.dim, return_labels=False)
    D = distributed_pairwise(X, kind="euclidean", mesh=mesh)
    require(_on_own_devices(D, p),
            f"the distance rows did not land one block per device: "
            f"{[str(s.device) for s in D.addressable_shards]}")
    D = np.asarray(D)
    res, cold = timed(lambda: distributed_lance_williams(D, "complete",
                                                         mesh=mesh))
    _, warm = timed(lambda: distributed_lance_williams(D, "complete",
                                                       mesh=mesh))
    got = np.asarray(res.merges)[: int(res.n_merges)]
    one = cluster(D, "complete", algorithm="lw", backend="serial",
                  keep_inputs=False)
    require(np.array_equal(got, one.merges),
            f"row-sharded LW on {p} devices differs from one device")
    report("four-lw", n=n, devices=p, cold_s=f"{cold:.3f}",
           warm_s=f"{warm:.3f}", devices_used=len(jax.devices()))


def phase_four_chain(sizes: Sizes, seed: int, ctx: dict) -> None:
    """The sharded matrix-free chain vs the serial chain, bit for bit."""
    from repro.core import nn_chain_from_points
    from repro.core.distributed import (
        distributed_nn_chain_from_points,
        make_cluster_mesh,
    )
    from repro.data.synthetic import gaussian_mixture
    from repro.distributed.sharding import shard_rows

    n = sizes.four_chain_n
    mesh = make_cluster_mesh()
    p = mesh.devices.size
    X = gaussian_mixture(seed + 5, n, sizes.dim, k=sizes.corpus_k,
                         return_labels=False)
    require(_on_own_devices(shard_rows(X, mesh), p),
            "the point blocks did not land one per device")
    res, t = timed(lambda: distributed_nn_chain_from_points(X, "ward",
                                                            mesh=mesh))
    one, t_one = timed(lambda: nn_chain_from_points(X, "ward"))
    got = np.asarray(res.merges)[: int(res.n_merges)]
    want = np.asarray(one.merges)[: int(one.n_merges)]
    require(got.shape == (n - 1, 4), f"sharded chain made {got.shape[0]} merges")
    require(np.array_equal(got, want),
            f"sharded chain on {p} devices differs from the serial chain")
    report("four-chain", n=n, d=sizes.dim, devices=p,
           sharded_cold_s=f"{t:.3f}", serial_cold_s=f"{t_one:.3f}")


def phase_four_batch(sizes: Sizes, seed: int, ctx: dict) -> None:
    """``cluster_batch`` on the mesh (problems spread over the devices)."""
    import jax

    from repro.core import build_distance_matrix, cluster, cluster_batch

    rng = np.random.default_rng(seed + 6)
    mats = [
        np.asarray(build_distance_matrix(
            rng.normal(size=(int(rng.integers(3, 65)), sizes.dim))
        ), np.float32)
        for _ in range(sizes.four_batch)
    ]
    res, cold = timed(lambda: cluster_batch(mats, "complete"))
    _, warm = timed(lambda: cluster_batch(mats, "complete"))
    require(res.stats.engine == "distributed",
            f"cluster_batch on {len(jax.devices())} devices ran the "
            f"{res.stats.engine!r} engine")
    for m, r in zip(mats, res):
        require(np.array_equal(r.merges,
                               cluster(m, "complete", backend="serial").merges),
                f"batched result for n={m.shape[0]} differs from one device")
    report("four-batch", problems=len(mats), devices=len(jax.devices()),
           cold_s=f"{cold:.3f}", warm_s=f"{warm:.3f}")


FOUR_CHIPS = (phase_four_lw, phase_four_chain, phase_four_batch)


# ---------------------------------------------------------------------------


def run_phases(phases, sizes: Sizes, seed: int) -> list[str]:
    """Run every phase; return the names of those that failed."""
    failed, ctx = [], {}
    for phase in phases:
        name = phase.__name__.removeprefix("phase_").replace("_", "-")
        t0 = time.perf_counter()
        try:
            phase(sizes, seed, ctx)
        except Exception:  # noqa: BLE001 - a phase failure is reported, not fatal
            traceback.print_exc()
            print(f"[{name}] FAILED after {time.perf_counter() - t0:.1f}s",
                  flush=True)
            failed.append(name)
        else:
            print(f"[{name}] passed in {time.perf_counter() - t0:.1f}s",
                  flush=True)
    return failed


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-device paths, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("no TPU found: this smoke run does not fall back to the CPU",
              file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"needs {want} chips, found {len(devices)}", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    phases = FOUR_CHIPS if args.four_chips else ONE_CHIP
    failed = run_phases(phases, Sizes(), args.seed)
    if failed:
        print(f"failed phases: {', '.join(failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
