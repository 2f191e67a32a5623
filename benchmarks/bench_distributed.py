"""Distributed matrix-free NN-chain: scaling + storage + two-phase quality.

The claims this bench measures (EXPERIMENTS.md §Perf-7, DESIGN.md §12):

* **equivalence** — the sharded chain's merges equal the serial points
  chain's bit-for-bit, for p ∈ {1, 2, 4} (asserted, not eyeballed);
* **storage** — per-device bytes are O(n·d/p + n): measured from the
  actual addressable shards across an n-sweep (to n ≥ 2·10⁵) and a
  p-sweep at fixed n, validated against the closed-form model that the
  n = 10⁶ row extrapolates from;
* **no dense buffer** — the compiled HLO of the chain program contains
  no ``(n_pad, n_pad)`` and no ``(n_pad/p, n_pad)`` f32 allocation (the
  paper's O(n²/p) matrix tier is exactly what this engine drops);
* **two-phase quality** — the approximate tier's merge-set agreement
  with the exact engine is *measured* on separated-mixture data.

Probes run in subprocesses (``--xla_force_host_platform_device_count``)
so the collectives are real; each prints one JSON line.  Output follows
the ``name,us_per_call,derived`` CSV convention ``run.py --json``
parses; rows with no meaningful timing carry the measured quantity in
``derived`` and 0 in the timing field.
"""

from __future__ import annotations

import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(_ROOT, "src")
if SRC not in sys.path:          # standalone `python benchmarks/...` use
    sys.path.insert(0, SRC)

from repro.fake_devices import CPU_ROWS_NOTE, run_on_cpu_devices  # noqa: E402

# per-device replicated O(n) state, bytes per padded slot: u (f32) +
# alive (bool) + sizes (f32) + chain (i32) + merges (4×f32) ≈ 29 B —
# the storage model the n=10⁶ row extrapolates from (validated against
# the measured probes below before use)
_REPL_BYTES_PER_SLOT = 29


def _model_bytes(n_pad: int, d: int, p: int) -> int:
    return 4 * n_pad * d // p + _REPL_BYTES_PER_SLOT * n_pad


def _run_probe(snippet: str, p: int, timeout: int = 560):
    out = run_on_cpu_devices(snippet, p, timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(
            f"probe failed (p={p}):\n{out.stderr[-2000:]}"
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


_EQ_SNIPPET = r"""
import json, time
import numpy as np, jax
from repro.core.nnchain import nn_chain_from_points
from repro.core.distributed import distributed_nn_chain_from_points
n, d = {n}, {d}
rng = np.random.default_rng(0)
X = rng.normal(size=(n, d)).astype(np.float32)
ser = np.asarray(nn_chain_from_points(X, "ward").merges)
res = distributed_nn_chain_from_points(X, "ward")     # compiles
equal = bool(np.array_equal(ser, np.asarray(res.merges)))
t0 = time.perf_counter()
r2 = distributed_nn_chain_from_points(X, "ward")
np.asarray(r2.merges)                                  # sync
wall = time.perf_counter() - t0
print(json.dumps({{"p": jax.device_count(), "n": n, "equal": equal,
                   "wall_s": wall}}))
"""

_STORAGE_SNIPPET = r"""
import json, time
import numpy as np, jax, jax.numpy as jnp
from repro.core import distributed as dist
n, d, trips = {n}, {d}, {trips}
mesh = dist.require_ring_mesh(None)
p = int(mesh.devices.size)
n_pad = dist.pad_to_mesh(n, p)
rng = np.random.default_rng(1)
X = jnp.asarray(rng.normal(size=(n_pad, d)).astype(np.float32))
from repro.distributed.sharding import replicate, shard_rows
alive = jnp.arange(n_pad) < n
state = (
    shard_rows(X, mesh),
    replicate(jnp.zeros((n_pad,), jnp.float32), mesh),
    replicate(alive, mesh),
    replicate(alive.astype(jnp.float32), mesh),
    replicate(jnp.zeros((n_pad,), jnp.int32), mesh),
    replicate(jnp.zeros((), jnp.int32), mesh),
    replicate(jnp.zeros((n - 1, 4), jnp.float32), mesh),
    replicate(jnp.zeros((), jnp.int32), mesh),
    replicate(jnp.zeros((), jnp.int32), mesh),
)
# measured storage: bytes device 0 actually addresses.  The sharded W
# contributes n·d/p; every replicated O(n) vector contributes fully.
dev0 = mesh.devices.flat[0]
def dev0_bytes(arr):
    return sum(s.data.nbytes for s in arr.addressable_shards
               if s.device == dev0)
bytes_per_device = sum(dev0_bytes(a) for a in state)

static = dict(method="ward", mesh=mesh, use_pallas=False,
              block_n=512, interpret=False)
lowered = dist._run_sharded_chain.lower(
    *state, jnp.asarray(trips, jnp.int32), **static)
compiled = lowered.compile()
hlo = compiled.as_text()
rows = n_pad // p
banned = [f"f32[{{n_pad}},{{n_pad}}]", f"f32[{{rows}},{{n_pad}}]"]
dense_hits = [b for b in banned if b in hlo]
try:
    ma = compiled.memory_analysis()
    temp_bytes = int(ma.temp_size_in_bytes)
except Exception:
    temp_bytes = -1
# run a bounded number of real chain trips and time them
state = dist._run_sharded_chain(
    *state, jnp.asarray(trips, jnp.int32), **static)
int(state[8])                                          # sync (iters)
t0 = time.perf_counter()
state = dist._run_sharded_chain(
    *state, jnp.asarray(2 * trips, jnp.int32), **static)
iters = int(state[8])                                  # sync
wall = time.perf_counter() - t0
print(json.dumps({{"p": p, "n": n, "n_pad": n_pad, "d": d,
                   "bytes_per_device": int(bytes_per_device),
                   "temp_bytes": temp_bytes,
                   "dense_hits": dense_hits,
                   "us_per_trip": wall / max(iters, 1) * 1e6}}))
"""


def main(*, smoke: bool = False, paper: bool = False):
    print(CPU_ROWS_NOTE)
    d = 16
    if smoke:
        eq_ns, eq_ps = 256, (2,)
        sweep_n, sweep_p = (4096,), 2
        psweep_n, psweep_ps = 4096, (1, 2)
        tp_n, tp_shards = 512, 4
    else:
        eq_ns, eq_ps = 512, (1, 2, 4)
        sweep_n = (20_000, 50_000, 100_000, 200_000)
        sweep_p = 4
        psweep_n, psweep_ps = 50_000, (1, 2, 4)
        tp_n, tp_shards = 2048, 8

    print("name,us_per_call,derived")

    # -- equivalence + wall clock, p-sweep (the correctness gate) -------
    for p in eq_ps:
        r = _run_probe(_EQ_SNIPPET.format(n=eq_ns, d=d), p)
        assert r["equal"], f"sharded chain diverged from serial at p={p}"
        print(f"dist_nnchain_equiv_p{p}_n{eq_ns},"
              f"{r['wall_s'] * 1e6:.0f},equal=True")

    # -- storage n-sweep at fixed p (the headline O(n·d/p + n) curve) ---
    trips = 32
    for n in sweep_n:
        r = _run_probe(_STORAGE_SNIPPET.format(n=n, d=d, trips=trips),
                       sweep_p)
        assert not r["dense_hits"], (
            f"compiled HLO allocates a dense buffer at n={n}: "
            f"{r['dense_hits']}"
        )
        model = _model_bytes(r["n_pad"], d, r["p"])
        # the model must track the measurement (it feeds the n=10⁶ row)
        ratio = r["bytes_per_device"] / model
        assert 0.8 < ratio < 1.25, (n, r["bytes_per_device"], model)
        print(f"dist_nnchain_mem_p{r['p']}_n{n},{r['us_per_trip']:.0f},"
              f"bytes_per_device={r['bytes_per_device']};model={model};"
              f"temp_bytes={r['temp_bytes']};no_dense_buffer=True")

    # -- storage p-sweep at fixed n (per-device memory ~ 1/p on W) ------
    base = None
    for p in psweep_ps:
        r = _run_probe(_STORAGE_SNIPPET.format(n=psweep_n, d=d,
                                               trips=trips), p)
        assert not r["dense_hits"], r["dense_hits"]
        if base is None:
            base = r["bytes_per_device"]
        print(f"dist_nnchain_mem_p{p}_n{psweep_n},{r['us_per_trip']:.0f},"
              f"bytes_per_device={r['bytes_per_device']};"
              f"reduction_vs_p{psweep_ps[0]}="
              f"{base / r['bytes_per_device']:.2f}x")

    # -- n = 10⁶ row: extrapolated from the validated model -------------
    for p in (4, 16, 64):
        n_pad = -(-1_000_000 // p) * p
        print(f"dist_nnchain_model_p{p}_n1000000,0,"
              f"model_bytes_per_device={_model_bytes(n_pad, d, p)};"
              f"extrapolated=True")

    # -- two-phase approximate tier: measured quality + speed -----------
    import numpy as np

    from repro.core import dendrogram as dg
    from repro.core.distributed import two_phase_from_points
    from repro.core.nnchain import nn_chain_from_points

    rng = np.random.default_rng(2)
    k = 16
    centers = rng.normal(size=(k, d)).astype(np.float32) * 20
    X = np.concatenate(
        [c + 0.1 * rng.normal(size=(tp_n // k, d)).astype(np.float32)
         for c in centers])
    t0 = time.perf_counter()
    exact = dg.canonical_order(
        np.asarray(nn_chain_from_points(X, "ward").merges), n=len(X))
    t_exact = time.perf_counter() - t0
    t0 = time.perf_counter()
    approx = np.asarray(
        two_phase_from_points(X, "ward", shards=tp_shards).merges)
    t_two = time.perf_counter() - t0
    agr = dg.merge_set_agreement(exact, approx, n=len(X))
    assert agr >= 0.5, f"two-phase agreement collapsed: {agr}"
    print(f"twophase_ward_n{len(X)}_s{tp_shards},{t_two * 1e6:.0f},"
          f"agreement={agr:.4f};exact_us={t_exact * 1e6:.0f}")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--paper", action="store_true")
    a = ap.parse_args()
    main(smoke=a.smoke, paper=a.paper)
