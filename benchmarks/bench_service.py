"""Service steady-state bench: throughput, latency tails, recompile count.

Measures the DESIGN.md §10 serving path end to end — warmup compiles
the declared working set, then a timed open-loop Poisson load of ragged
problems (sizes drawn from inside the declared buckets) runs through
the micro-batching front-end.  The derived column carries the §10
invariant: ``steady_compiles`` and ``steady_jit_growth`` must both be
ZERO after warmup, and the bench **fails** (non-zero exit through
``run.py``) if they are not — the CI smoke step is a recompile
regression gate, not just a timing readout.

A second section A/Bs the batched NN-chain buckets (DESIGN.md §11)
against the LW-bucket baseline on reducible ward *points* traffic
(bucket 128 — where the matrix-free O(n·d) pad-waste argument bites):
two identically-configured services, closed-loop saturation load, with
per-lane dendrogram equivalence (``canonical_order`` semantics via
``merges_equivalent``) asserted BEFORE timing.  The bench **fails** if
the nnchain service does not clear ≥1.5x the LW req/s — the routing
regression gate for ``algorithm="auto"``.

``main_overload`` (its own ``run.py`` suite, ``--only
service_overload``) runs the DESIGN.md §14 overload sweep: closed-loop
capacity probe, then open-loop load at 0.5×–4× capacity through the
shed-oldest / 3-lane / deadline posture of
``repro.service.server.overload_config``.  It **fails** unless
p99-of-admitted stays within ``OVERLOAD_P99_GATE`` of the 1× p99,
shedding stays confined to the lowest lane, goodput holds above
``OVERLOAD_GOODPUT_FLOOR`` of capacity, and every decline is typed.

    PYTHONPATH=src python benchmarks/bench_service.py [--smoke] [--rate R]
"""

from __future__ import annotations

import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


#: A/B gate: nnchain buckets must clear this speedup over the LW-bucket
#: baseline on reducible ward points traffic (measured: 4–7x at bucket
#: 128 before submit-path matrix-build savings are counted).
NNCHAIN_AB_GATE = 1.5

#: Instrumentation gate (DESIGN.md §13): full tracing may cost at most
#: this fraction of the uninstrumented service's throughput (measured:
#: well under 1% — spans are a few host-side perf_counter reads per
#: request against a ~ms engine dispatch).
OBS_OVERHEAD_GATE = 0.05

#: Overload gates (DESIGN.md §14): at 4× capacity, p99-of-admitted may
#: be at most this multiple of the 1× p99 (the bounded queue + deadlines
#: must keep admitted latency flat while shedding absorbs the excess)...
OVERLOAD_P99_GATE = 2.0
#: ...and goodput at 4× must hold at least this fraction of capacity
#: (shedding exists to PROTECT throughput; a collapse here means the
#: admission path itself became the bottleneck).
OVERLOAD_GOODPUT_FLOOR = 0.35


def ab_instrumentation_overhead(smoke: bool = False):
    """Closed-loop A/B: identical services, tracing on vs off.

    Interleaves the two modes at single-pass (~10 ms) granularity —
    off, on, off, on, ... — and gates on the **median of the paired
    per-pass ratios**, so a background-load blip (hits one pair, not
    the median) and machine-wide drift (hits both sides of a pair
    equally) cancel instead of deciding the gate.  While it's at it,
    the traced side re-proves the
    §10 invariant under instrumentation (zero steady compiles) and the
    exported trace is checked for full request coverage: every request
    id appears in a ``submit`` and a ``resolve`` span and is packed into
    exactly one ``bucket`` whose dispatch produced ``pack`` / ``cache``
    / ``execute`` spans.

    Returns ``(off_rps, on_rps, overhead_frac, n_traced_spans)``.
    """
    import time

    import numpy as np

    from repro.obs import Tracer
    from repro.service.batcher import ClusteringService, ServiceConfig
    from repro.service.server import synthetic_problem

    rng = np.random.default_rng(1)
    sizes = (5, 8, 12, 20, 27)
    # each timed rep drains the pool `passes` times so one rep is tens of
    # milliseconds — long enough that scheduler jitter does not decide a
    # 5% gate on a ~1% effect
    pool_n, passes, reps = (24, 8, 7) if smoke else (48, 10, 9)
    pool = [
        synthetic_problem(rng, int(rng.choice(sizes))) for _ in range(pool_n)
    ]
    config = ServiceConfig(
        method="complete", engine="serial",
        max_batch=8, max_delay_ms=1.0, bucket_ns=(8, 16, 32),
    )
    tracer = Tracer()
    services = {
        "off": ClusteringService(config),
        "on": ClusteringService(config, tracer=tracer),
    }
    rep_rps = {"off": [], "on": []}
    try:
        for svc in services.values():
            svc.warmup()
            # one untimed closed-loop pass per service: first-touch costs
            # (allocator, thread scheduling) land outside the A/B
            for fut in svc.submit_many(pool[:8], is_distance=True):
                fut.result(timeout=600)
        compiles_before = services["on"].cache.stats.compiles
        traced_served = 0
        for pair in range(reps * passes):
            # swap the within-pair order each time so a "whoever runs
            # second is warmer" bias cancels across the pairs
            order = ("off", "on") if pair % 2 == 0 else ("on", "off")
            times = {}
            for mode in order:
                svc = services[mode]
                t0 = time.perf_counter()
                futures = svc.submit_many(pool, is_distance=True)
                for fut in futures:
                    fut.result(timeout=600)
                times[mode] = time.perf_counter() - t0
                if mode == "on":
                    traced_served += len(futures)
            for mode, dt in times.items():
                rep_rps[mode].append(pool_n / dt)
        traced_compiles = services["on"].cache.stats.compiles - compiles_before
    finally:
        for svc in services.values():
            svc.close()
    if traced_compiles:
        raise RuntimeError(
            f"tracing-on service performed {traced_compiles} steady-state "
            "compiles — instrumentation broke the §10 zero-recompile "
            "contract (it must stay host-side)"
        )

    # full-coverage check on the traced side's span story
    events = tracer.events()
    by_name = {}
    for e in events:
        by_name.setdefault(e.name, []).append(e)
    submit_ids = {e.args["trace_id"] for e in by_name.get("submit", ())}
    resolve_ids = {e.args["trace_id"] for e in by_name.get("resolve", ())}
    bucket_ids = {
        tid for e in by_name.get("bucket", ()) for tid in e.args["trace_ids"]
    }
    if not (submit_ids and submit_ids == resolve_ids
            and submit_ids <= bucket_ids):
        raise RuntimeError(
            f"trace coverage broken: {len(submit_ids)} submit ids, "
            f"{len(resolve_ids)} resolve ids, {len(bucket_ids)} bucketed ids "
            "— every request must appear in submit, bucket and resolve spans"
        )
    n_buckets = len(by_name.get("bucket", ()))
    for kind in ("pack", "cache", "execute"):
        if len(by_name.get(kind, ())) != n_buckets:
            raise RuntimeError(
                f"trace coverage broken: {len(by_name.get(kind, ()))} "
                f"{kind!r} spans for {n_buckets} bucket dispatches"
            )
    # median of the paired ratios: each pair ran back-to-back, so drift
    # cancels within a pair and a one-rep blip cannot move the median
    ratios = sorted(
        on / off for off, on in zip(rep_rps["off"], rep_rps["on"]) if off
    )
    med_ratio = ratios[len(ratios) // 2] if ratios else 1.0
    overhead = max(1.0 - med_ratio, 0.0)
    off_rps = max(rep_rps["off"], default=0.0)
    return off_rps, off_rps * med_ratio, overhead, len(events)


def ab_nnchain_vs_lw(smoke: bool = False) -> tuple[float, float]:
    """Closed-loop ward-points A/B: LW buckets vs matrix-free nnchain.

    Returns ``(lw_rps, nnchain_rps)``.  Identical traffic, identical
    batching policy; only ``algorithm``/``points_dim`` differ.  The LW
    service builds each request's (n, n) matrix on the submit path —
    part of the honest end-to-end cost the nnchain path never pays.
    """
    import time

    import numpy as np

    from repro.core import cluster
    from repro.core import dendrogram as dg
    from repro.service.batcher import ClusteringService, ServiceConfig

    rng = np.random.default_rng(0)
    sizes, dim = (65, 80, 100, 128), 8
    pool_n, reps = (16, 2) if smoke else (32, 5)
    pool = [
        rng.normal(size=(int(rng.choice(sizes)), dim)).astype(np.float32)
        for _ in range(pool_n)
    ]
    rps = {}
    for algo, pdim in (("lw", None), ("nnchain", dim)):
        config = ServiceConfig(
            method="ward", engine="serial", algorithm=algo, points_dim=pdim,
            max_batch=8, max_delay_ms=1.0, bucket_ns=(128,),
        )
        with ClusteringService(config) as svc:
            svc.warmup()
            # per-lane dendrogram equivalence gate BEFORE any timing: both
            # services must reproduce the serial LW tree per problem
            for X, fut in zip(pool[:4], svc.submit_many(pool[:4])):
                res = fut.result(timeout=600)
                want = cluster(X, "ward", algorithm="lw", backend="serial")
                if not dg.merges_equivalent(res.merges, want.merges,
                                            n=X.shape[0]):
                    raise RuntimeError(
                        f"A/B equivalence gate failed: {algo} service "
                        f"diverged from serial LW on n={X.shape[0]}"
                    )
            t0 = time.perf_counter()
            served = 0
            for _ in range(reps):
                futures = svc.submit_many(pool)
                for fut in futures:
                    fut.result(timeout=600)
                served += len(futures)
            rps[algo] = served / (time.perf_counter() - t0)
    return rps["lw"], rps["nnchain"]


def main(rate: float = 300.0, duration: float = 3.0, smoke: bool = False):
    from repro.service.batcher import ServiceConfig
    from repro.service.server import drive

    if smoke:
        rate, duration = 100.0, 1.0
    config = ServiceConfig(
        method="complete",
        engine="serial",
        max_batch=8,
        max_delay_ms=2.0,
        bucket_ns=(8, 16, 32),
    )
    report = drive(
        config,
        rate_hz=rate,
        duration_s=duration,
        sizes=(5, 8, 12, 20, 27),
        seed=0,
    )
    s = report.snapshot
    us_per_req = (
        report.elapsed_s / report.n_submitted * 1e6 if report.n_submitted else 0.0
    )
    print("name,us_per_call,derived")
    print(f"service_throughput,{us_per_req:.0f},"
          f"{report.throughput_rps:.1f}req/s")
    print(f"service_p50,{s.p50_ms * 1e3:.0f},latency_p50")
    print(f"service_p99,{s.p99_ms * 1e3:.0f},latency_p99")
    print(f"service_batching,{0:.0f},mean_batch={s.mean_batch_size:.2f};"
          f"pad_waste={s.pad_waste:.2f}")
    print(f"service_cache,{0:.0f},hit_rate={s.cache_hit_rate:.3f};"
          f"warmup_compiles={report.warmup_compiles}")
    print(f"service_steady_compiles,{0:.0f},"
          f"aot={report.steady_compiles};jit={report.steady_jit_growth}")
    if report.n_errors or report.n_unresolved:
        raise RuntimeError(
            f"{report.n_errors} requests failed, "
            f"{report.n_unresolved} never resolved"
        )
    if report.steady_compiles or report.steady_jit_growth:
        raise RuntimeError(
            "steady-state traffic compiled after warmup "
            f"(aot={report.steady_compiles}, jit={report.steady_jit_growth}) "
            "— the §10 zero-recompile invariant regressed"
        )

    lw_rps, nn_rps = ab_nnchain_vs_lw(smoke=smoke)
    speedup = nn_rps / lw_rps if lw_rps else 0.0
    print(f"service_ab_lw_ward_points,{1e6 / lw_rps:.0f},"
          f"{lw_rps:.1f}req/s")
    print(f"service_ab_nnchain_ward_points,{1e6 / nn_rps:.0f},"
          f"{nn_rps:.1f}req/s;speedup={speedup:.2f}x")
    if speedup < NNCHAIN_AB_GATE:
        raise RuntimeError(
            f"nnchain buckets {speedup:.2f}x vs LW baseline on reducible "
            f"ward points traffic — below the {NNCHAIN_AB_GATE}x gate "
            "(algorithm='auto' routing or the batched chain regressed)"
        )

    off_rps, on_rps, overhead, n_spans = ab_instrumentation_overhead(
        smoke=smoke)
    if overhead > OBS_OVERHEAD_GATE:
        # a shared-machine blip can push a ~1% effect past 5% once; a
        # real instrumentation regression fails the re-measure too
        print(f"# obs overhead {overhead:.3f} > gate on first measure — "
              "re-measuring once")
        off_rps, on_rps, overhead, n_spans = ab_instrumentation_overhead(
            smoke=smoke)
    print(f"service_obs_off,{1e6 / off_rps:.0f},{off_rps:.1f}req/s")
    print(f"service_obs_on,{1e6 / on_rps:.0f},{on_rps:.1f}req/s;"
          f"overhead={overhead:.3f};spans={n_spans}")
    if overhead > OBS_OVERHEAD_GATE:
        raise RuntimeError(
            f"full tracing costs {overhead:.1%} of service throughput — "
            f"above the {OBS_OVERHEAD_GATE:.0%} instrumentation gate "
            "(a span landed on the hot path or inside compiled code?)"
        )
    return report


def _overload_gates(report) -> list[str]:
    """Check one sweep report against the §14 gates; return violations."""
    lo, hi = report.point(1.0), report.point(4.0)
    lowest = len(hi.shed_by_lane) - 1
    violations = []
    ratio = (hi.p99_admitted_ms / lo.p99_admitted_ms
             if lo.p99_admitted_ms else 0.0)
    if ratio > OVERLOAD_P99_GATE:
        violations.append(
            f"p99-of-admitted at 4x is {ratio:.2f}x the 1x p99 "
            f"({hi.p99_admitted_ms:.1f} vs {lo.p99_admitted_ms:.1f} ms) — "
            f"above the {OVERLOAD_P99_GATE}x gate (admitted latency must "
            "stay flat under overload; is the queue bound or deadline "
            "enforcement broken?)"
        )
    if hi.goodput_rps < OVERLOAD_GOODPUT_FLOOR * report.capacity_rps:
        violations.append(
            f"goodput at 4x collapsed to {hi.goodput_rps:.0f} req/s "
            f"({hi.goodput_rps / report.capacity_rps:.0%} of the "
            f"{report.capacity_rps:.0f} req/s capacity, floor "
            f"{OVERLOAD_GOODPUT_FLOOR:.0%}) — shedding is costing more "
            "than it saves"
        )
    for p in report.points:
        spilled = sum(p.shed_by_lane[:lowest])
        if spilled:
            violations.append(
                f"at {p.multiple:g}x, {spilled} requests were shed/expired "
                f"from lanes above the lowest (shed_by_lane="
                f"{list(p.shed_by_lane)}) — load shedding must stay "
                "confined to the lowest priority class"
            )
        if p.n_failed:
            violations.append(
                f"at {p.multiple:g}x, {p.n_failed} requests failed with an "
                "untyped error — overload must resolve as typed "
                "ServiceOverloaded/DeadlineExceeded, never a crash"
            )
        if p.n_unresolved:
            violations.append(
                f"at {p.multiple:g}x, {p.n_unresolved} requests were still "
                "pending after the flush — every future must resolve"
            )
    half = report.point(0.5)
    if half.shed_rate > 0.05:
        violations.append(
            f"at 0.5x capacity {half.shed_rate:.1%} of requests were shed — "
            "admission control is rejecting traffic the service can serve"
        )
    return violations


def main_overload(smoke: bool = False):
    """§14 overload sweep: capacity probe, then 0.5×–4× open-loop points.

    Emits one CSV row per sweep point and hard-fails on the acceptance
    gates (p99-of-admitted flat within ``OVERLOAD_P99_GATE``, shedding
    confined to the lowest lane, no goodput collapse, no untyped
    failures).  Like the obs-overhead gate, a first miss re-measures
    once before failing — the gates compare two latency tails from short
    runs, and a shared-machine blip should not fail CI on its own.
    """
    from repro.service.server import overload_config, overload_sweep

    duration, capacity_s = (1.2, 1.0) if smoke else (2.0, 1.5)
    report = overload_sweep(
        overload_config(), duration_s=duration, capacity_s=capacity_s,
    )
    violations = _overload_gates(report)
    if violations:
        print(f"# overload gates missed on first measure "
              f"({len(violations)}) — re-measuring once")
        report = overload_sweep(
            overload_config(), duration_s=duration, capacity_s=capacity_s,
            seed=1,
        )
        violations = _overload_gates(report)
    print("name,us_per_call,derived")
    print(f"service_overload_capacity,{1e6 / report.capacity_rps:.0f},"
          f"{report.capacity_rps:.0f}req/s")
    for p in report.points:
        tag = f"{p.multiple:g}".replace(".", "p")
        print(
            f"service_overload_{tag}x,"
            f"{1e6 / p.goodput_rps if p.goodput_rps else 0:.0f},"
            f"goodput={p.goodput_rps:.0f}req/s;shed={p.shed_rate:.2f};"
            f"expired={p.n_expired};p99_admitted={p.p99_admitted_ms:.1f}ms"
        )
    lo, hi = report.point(1.0), report.point(4.0)
    ratio = (hi.p99_admitted_ms / lo.p99_admitted_ms
             if lo.p99_admitted_ms else 0.0)
    print(f"service_overload_p99_admitted_4x,{hi.p99_admitted_ms * 1e3:.0f},"
          f"ratio_vs_1x={ratio:.2f}x;gate<={OVERLOAD_P99_GATE}x;"
          f"shed_by_lane={'/'.join(str(s) for s in hi.shed_by_lane)}")
    if violations:
        raise RuntimeError(
            "overload sweep failed the §14 gates:\n  - "
            + "\n  - ".join(violations)
        )
    return report


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--rate", type=float, default=300.0)
    ap.add_argument("--duration", type=float, default=3.0)
    ap.add_argument("--smoke", action="store_true",
                    help="short run; verifies the zero-recompile gate")
    ap.add_argument("--overload", action="store_true",
                    help="run only the §14 overload sweep + gates")
    a = ap.parse_args()
    if a.overload:
        main_overload(smoke=a.smoke)
    else:
        main(rate=a.rate, duration=a.duration, smoke=a.smoke)
