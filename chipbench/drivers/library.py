"""Driver for library configurations: trees back to back through ``cluster``.

Set-up draws ``pool`` distinct point sets and one more for the warm-up
tree, all from the seed, and builds the warm-up tree, which loads (or, in
a checkout's first run, compiles) every program a tree runs.  The window
then starts trees back to back while it has time left, each on the next
point set, so it may overrun by one tree.  A tree's time runs from the
``cluster`` call to its merges as a host array.
"""

from __future__ import annotations

import time

import numpy as np

from chipbench.generate import gaussian_mixture, rng_for


def point_sets(run) -> tuple[np.ndarray, list[np.ndarray]]:
    """The warm-up set and the window's pool, from the seed."""
    cfg, trf = run.config, run.traffic
    n, d, k = cfg["n_points"], cfg["dim"], cfg["components"]
    warm = gaussian_mixture(rng_for(run.seed, 0), n, d, k)
    pool = [gaussian_mixture(rng_for(run.seed, 1, i), n, d, k)
            for i in range(trf["pool"])]
    return warm, pool


def sampled(run, count: int) -> list[int]:
    """The trees of the window that the reference checks, drawn from the
    seed (``check_trees`` of them)."""
    k = min(count, run.traffic["check_trees"])
    return sorted(rng_for(run.seed, 2).choice(count, size=k, replace=False)
                  .tolist())


def check_inputs(run) -> list[np.ndarray]:
    """Point sets whose trees the reference checks, for the control."""
    _, pool = point_sets(run)
    return [pool[i] for i in sampled(run, len(pool))]


def run(run) -> dict:
    from repro.core import cluster

    cfg = run.config
    method, kwargs = cfg["method"], cfg.get("cluster", {})
    with run.annotate("prepare"):
        warm, pool = point_sets(run)
    try:
        np.asarray(cluster(warm, method, **kwargs).merges)
    except Exception as exc:  # noqa: BLE001 - the window's trees are judged
        print(f"warm-up tree failed: {exc!r}", flush=True)

    t0 = run.open_window()
    t_end = t0 + run.seconds
    durations, trees, failed = [], [], 0
    while time.perf_counter() < t_end:
        X = pool[len(trees) % len(pool)]
        t1 = time.perf_counter()
        try:
            with run.annotate("cluster"):
                merges = np.asarray(cluster(X, method, **kwargs).merges)
        except Exception as exc:  # noqa: BLE001 - a failed tree is counted
            print(f"tree {len(trees)} failed: {exc!r}", flush=True)
            failed += 1
            trees.append(None)
            continue
        durations.append(time.perf_counter() - t1)
        trees.append(merges)
    run.close_window()

    # every failed tree counts against correct, besides the sampled ones
    checked = set(sampled(run, len(trees)))
    checked |= {i for i, t in enumerate(trees) if t is None}
    return {
        "attempted": len(trees),
        "failed": failed,
        "tree_durations_s": durations,
        "answers": [(pool[i % len(pool)], trees[i]) for i in sorted(checked)],
        "notes": {"trees": len(trees),
                  "pool_reused": len(trees) > len(pool)},
    }
