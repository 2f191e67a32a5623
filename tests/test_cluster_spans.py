"""Spans and the chain histograms of ``cluster`` (DESIGN.md §13): the
``repro/cluster`` phases on the profiler's host plane, the sharded
chain's own phases inside ``cluster/engine``, one histogram observation
per phase per call, and the chain's trips and shards on the result and
the registry."""

import glob
import os

import jax
import numpy as np
import pytest

from repro.core import cluster
from repro.core.nnchain import nn_chain_from_points
from repro.obs import PHASE_SECONDS, Tracer, phase, reset_registry

CHAIN_PHASES = ("cluster", "cluster/input", "cluster/engine", "cluster/fetch",
                "cluster/canonical_order", "cluster/truncate",
                "cluster/result")
LW_PHASES = ("cluster", "cluster/input", "cluster/engine", "cluster/fetch",
             "cluster/result")
SHARDED_PHASES = ("sharded_chain/place", "sharded_chain/loop")
SHARDED = {"algorithm": "nnchain", "backend": "distributed"}


def _points(n=48, d=8, seed=0):
    return np.random.default_rng(seed).normal(size=(n, d)).astype(np.float32)


def _host_spans(log_dir: str) -> list[tuple[str, int, int]]:
    """``(name, start_ns, end_ns)`` of every ``repro/`` event on a host
    plane of the one trace under ``log_dir``."""
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("repro/")]


def _counts(reg, phases) -> dict[str, int]:
    hist = reg.get(PHASE_SECONDS)
    return {p: hist.count(phase=p) for p in phases}


def test_cluster_phases_nest_on_the_profiler_host_plane(tmp_path):
    X = _points()
    cluster(X, "ward", matrix_free=True)        # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        cluster(X, "ward", matrix_free=True)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    names = [name for name, _, _ in spans]
    assert sorted(names) == sorted("repro/" + p for p in CHAIN_PHASES)
    (parent,) = [s for s in spans if s[0] == "repro/cluster"]
    children = sorted((s for s in spans if s is not parent),
                      key=lambda s: s[1])
    for name, a, b in children:
        assert parent[1] <= a <= b <= parent[2], name
    # the children run one after another, in the order cluster() runs them
    assert [c[0] for c in children] == ["repro/" + p for p in CHAIN_PHASES[1:]]
    for (_, _, b), (_, a, _) in zip(children, children[1:]):
        assert b <= a


def test_tracer_span_reaches_the_profiler_host_plane(tmp_path):
    tracer = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracer.span("outer"):
            with tracer.span("inner"):
                pass
        tracer.add_span("after_the_fact", 0.0, 1e-3)
    finally:
        jax.profiler.stop_trace()
    names = {name for name, _, _ in _host_spans(str(tmp_path))}
    assert names == {"repro/outer", "repro/inner"}
    assert {e.name for e in tracer.events()} == {"outer", "inner",
                                                 "after_the_fact"}


@pytest.mark.parametrize("calls", [1, 3])
def test_each_call_adds_one_observation_per_phase(calls):
    reg = reset_registry()
    X = _points()
    for _ in range(calls):
        cluster(X, "ward", matrix_free=True)
    assert _counts(reg, CHAIN_PHASES) == dict.fromkeys(CHAIN_PHASES, calls)
    hist = reg.get(PHASE_SECONDS)
    # each phase lies inside the whole call
    for p in CHAIN_PHASES[1:]:
        assert hist.sum(phase=p) <= hist.sum(phase="cluster")


def test_chain_trips_on_result_equal_the_engine_and_the_registry():
    reg = reset_registry()
    X = _points(n=64, seed=1)
    res = cluster(X, "ward", matrix_free=True)
    iters = int(nn_chain_from_points(X, "ward").iters)
    assert res.chain_trips == iters
    # one trip per merge plus one per push; pushes ≤ 2 merges
    assert 64 - 1 < iters <= 3 * (64 - 1)
    assert reg.get("chain_trips").window() == [iters]
    assert reg.get("chain_trips").sum() == iters    # lifetime trips
    # the dense chain counts its trips too
    dense = cluster(X, "ward", algorithm="nnchain", matrix_free=False)
    assert dense.chain_trips is not None and dense.chain_trips > 0
    assert reg.get("chain_trips").count() == 2


def test_sharded_chain_reports_the_serial_chains_trips():
    reg = reset_registry()
    X = _points(n=40, seed=2)
    res = cluster(X, "ward", algorithm="nnchain", backend="distributed")
    assert res.chain_trips == int(nn_chain_from_points(X, "ward").iters)
    assert reg.get("chain_trips").window() == [res.chain_trips]


def test_sharded_chain_phases_nest_inside_the_engine_span(tmp_path):
    X = _points()
    cluster(X, "ward", **SHARDED)               # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        cluster(X, "ward", **SHARDED)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    assert sorted(name for name, _, _ in spans) == sorted(
        "repro/" + p for p in CHAIN_PHASES + SHARDED_PHASES)
    (engine,) = [s for s in spans if s[0] == "repro/cluster/engine"]
    place, loop = sorted((s for s in spans
                          if s[0].startswith("repro/sharded_chain/")),
                         key=lambda s: s[1])
    assert [place[0], loop[0]] == ["repro/" + p for p in SHARDED_PHASES]
    assert engine[1] <= place[1] <= place[2] <= loop[1] <= loop[2] <= engine[2]


@pytest.mark.parametrize("calls", [1, 3])
def test_sharded_chain_adds_one_place_and_loop_per_call(calls):
    reg = reset_registry()
    X = _points(n=40, seed=2)
    for _ in range(calls):
        cluster(X, "ward", **SHARDED)
    phases = CHAIN_PHASES + SHARDED_PHASES
    assert _counts(reg, phases) == dict.fromkeys(phases, calls)
    hist = reg.get(PHASE_SECONDS)
    engine = hist.window(phase="cluster/engine")
    for p in SHARDED_PHASES:
        assert all(0 < t < e for t, e in zip(hist.window(phase=p), engine))


CHAIN_ROUTES = {"matrix_free": {"matrix_free": True},
                "dense": {"algorithm": "nnchain", "matrix_free": False},
                "sharded": SHARDED}


@pytest.mark.parametrize("route", sorted(CHAIN_ROUTES))
def test_chain_shards_on_result_and_registry(route):
    """The mesh size on the sharded chain, 1 on the one-device chains."""
    reg = reset_registry()
    res = cluster(_points(n=40, seed=3), "ward", **CHAIN_ROUTES[route])
    shards = jax.device_count() if route == "sharded" else 1
    assert res.chain_shards == shards
    assert reg.get("chain_shards").window() == [shards]
    # only the sharded chain runs the sharded phases
    assert reg.get(PHASE_SECONDS).count(phase="sharded_chain/loop") == (
        route == "sharded")


@pytest.mark.parametrize("backend", ["serial", "kernel"])
def test_lw_call_records_its_spans_and_no_chain_trips(backend):
    reg = reset_registry()
    res = cluster(_points(n=24), "complete", algorithm="lw", backend=backend)
    assert res.chain_trips is None and res.chain_shards is None
    assert _counts(reg, LW_PHASES) == dict.fromkeys(LW_PHASES, 1)
    assert _counts(reg, ("cluster/canonical_order", "cluster/truncate")) == {
        "cluster/canonical_order": 0, "cluster/truncate": 0}
    assert reg.get("chain_trips") is None


@pytest.mark.parametrize("algorithm", ["landmark", "twophase"])
def test_approximate_tiers_truncate_and_report_no_chain_trips(algorithm):
    reg = reset_registry()
    res = cluster(_points(n=64), "ward", algorithm=algorithm, stop_at_k=4)
    assert res.chain_trips is None and res.n_merges == 64 - 4
    assert res.chain_shards is None and reg.get("chain_shards") is None
    phases = LW_PHASES + ("cluster/truncate",)
    assert _counts(reg, phases) == dict.fromkeys(phases, 1)
    assert reg.get("chain_trips") is None


def test_a_failed_call_still_closes_its_spans():
    reg = reset_registry()
    with pytest.raises(ValueError, match="matrix_free=True requires"):
        cluster(_points(), "ward", algorithm="lw", matrix_free=True)
    assert _counts(reg, ("cluster", "cluster/input", "cluster/engine")) == {
        "cluster": 1, "cluster/input": 1, "cluster/engine": 0}
    assert reg.get("chain_trips") is None


def test_phase_observes_into_the_registry_and_tracer_it_is_given():
    from repro.obs import MetricsRegistry

    reg, tracer = MetricsRegistry(), Tracer()
    with phase("outer", registry=reg, tracer=tracer):
        with phase("inner", registry=reg):
            pass
    hist = reg.get(PHASE_SECONDS)
    assert hist.count(phase="outer") == hist.count(phase="inner") == 1
    assert hist.sum(phase="inner") <= hist.sum(phase="outer")
    assert [(e.name, e.cat) for e in tracer.events()] == [("outer", "cluster")]
