"""Batched multi-problem Lance-Williams engines (DESIGN.md §9).

The paper scales ONE big problem across processors (``n²/p`` storage);
production traffic is the transpose: *millions of small problems* (one
dendrogram per user / per document shard / per protein family).  This
module clusters a whole batch of independent problems in a single
compiled program.  All three engines are execution wrappers around the
same unified merge loop (:mod:`repro.core.engine`):

* **serial engine** — ``jax.vmap`` of the dense composition: one
  dispatch, one loop, every problem advancing in lockstep on one device.
* **distributed engine** — whole problems assigned to mesh devices via
  ``shard_map`` (batch-axis sharding, ``P('p', None, None)``); each device
  vmaps over its local slice.  Zero inter-device communication — the
  embarrassingly parallel regime of Parallel D2-Clustering / clusterNOR,
  complementary to the paper's intra-problem sharding.
* **kernel engine** — ``jax.vmap`` of the Pallas composition (the
  ``pallas_call`` batching rule prepends the batch grid dimension), see
  :func:`repro.kernels.ops.lance_williams_kernelized_batch`.

Ragged batches are padded into **shape buckets** (:data:`BUCKETS`, the
powers of two from 8 to 4096: a small static grid of shapes so compiles
are amortized): problem ``n`` is rounded up to the next bucket, the
batch axis is rounded up to a power of two, and XLA's jit cache then
guarantees one compile per
``(bucket_n, bucket_B, method, engine, variant, compaction, algorithm)``
for the lifetime of the process (a compacted run's whole stage schedule
lives inside that one program).  Padded slots are born dead
(``alive=False``) and padded *problems* have ``n_real=0``.  The vmap and
shard_map engines emit merge lists bit-identical to the single-problem
serial engine; the kernel engine matches merge indices exactly with
distances equal to float tolerance (the single-problem kernel contract).
The engine-level ``variant`` / ``stop_at_k`` / ``distance_threshold``
knobs pass straight through to every engine.

A bucket may also run the **batched NN-chain engine** (DESIGN.md §11) —
``algorithm="nnchain"`` explicitly, or ``"auto"`` for matrix-free
points buckets of :data:`repro.core.nnchain.NNCHAIN_BATCH_AUTO_MIN_N`
or larger (the measured win; dense buckets keep LW under ``auto``).
NN-chain buckets are canonicalized: the signature pins
``n_steps = bucket_n − 1``, ``with_threshold=False``, baseline variant
and no compaction (the chain runs the full agglomeration and the
scheduler applies early stop post-hoc via
:func:`repro.core.dendrogram.truncate_canonical`), so one executable
serves every early-stop knob combination.  Their merge lists come back
*height-sorted* (:func:`repro.core.dendrogram.canonical_order`) —
equivalent to the LW lists (same clusters and heights to float
tolerance) but not bit-identical; pin ``algorithm="lw"`` where the LW
loop's row-major tie-breaking must be reproduced bit-for-bit.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.engine import (
    AXIS,
    VARIANTS,
    LWResult,
    resolve_compaction,
    run_dense,
    symmetrize,
)
from repro.core import dendrogram as dg
from repro.core import nnchain as _nnchain
from repro.core.linkage import METHODS
from repro.core.nnchain import resolve_batch_algorithm

#: Static padded-n grid (shape buckets).  Problems are rounded up to the
#: smallest bucket that fits; one compile per touched bucket.
BUCKETS: tuple[int, ...] = (8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096)


def bucket_n(n: int) -> int:
    """Smallest bucket that fits a problem of ``n`` items."""
    for b in BUCKETS:
        if n <= b:
            return b
    raise ValueError(
        f"problem size n={n} exceeds the largest batch bucket {BUCKETS[-1]}; "
        "cluster it with the single-problem distributed engine instead"
    )


def bucket_batch(b: int, multiple_of: int = 1) -> int:
    """Round a batch size up to a power of two, then up to a multiple of
    ``multiple_of`` (the device count for the sharded engine)."""
    out = max(1, 1 << (b - 1).bit_length())
    if multiple_of > 1 and out % multiple_of:
        out = -(-out // multiple_of) * multiple_of
    return out


@dataclass(frozen=True)
class BucketSignature:
    """Static compile signature of one bucket dispatch.

    Everything that determines *which compiled executable* serves a
    bucket — if two dispatches share a signature, XLA reuses one
    program.  The scheduler derives one per touched bucket; the serving
    layer's compile cache (:mod:`repro.service.cache`) uses it verbatim
    as the cache/warmup key.
    """

    bucket_n: int          # padded problem size (from the BUCKETS grid)
    bucket_B: int          # padded batch size (power of two × device multiple)
    method: str
    engine: str            # 'serial' | 'distributed' | 'kernel'
    variant: str
    n_steps: int           # static trip count = max(bucket_n - stop_at_k, 0)
    with_threshold: bool   # structural: while_loop vs fori_loop
    compaction: bool = False  # structural: staged vs single-stage loop
    algorithm: str = "lw"     # merge engine: 'lw' | 'nnchain'
    points_dim: int = 0       # >0: matrix-free (B, n, d) operands (nnchain)


def _resolve_bucket_compaction(flag, engine: str, bucket_n: int,
                               n_steps: int) -> bool:
    """Resolved (canonical) compaction flag for one bucket dispatch.

    The stage plan runs on the bucket's padded shape, so the switch is a
    *bucket* property: ``"auto"`` resolves identically for every request
    the bucket serves, and a degenerate plan (tiny bucket, lane floor)
    canonicalizes to ``False`` — one signature, one executable.  All
    stages of a compacted run live inside that one executable.
    """
    if engine == "kernel":
        from repro.kernels.ops import resolve_kernel_compaction

        return resolve_kernel_compaction(flag, bucket_n, n_steps)
    return resolve_compaction(flag, bucket_n, n_steps)


def bucket_signature(
    n: int,
    batch: int,
    *,
    method: str,
    engine: str = "serial",
    variant: str = "baseline",
    stop_at_k: int = 1,
    with_threshold: bool = False,
    b_multiple: int = 1,
    compaction: bool | str = "auto",
    algorithm: str = "lw",
    points_dim: int = 0,
) -> BucketSignature:
    """Signature of the bucket serving ``batch`` problems of ≤ ``n`` items.

    ``n`` rounds up to the bucket grid and ``batch`` to a power of two
    (times ``b_multiple``, the device count for the sharded engine) —
    exactly the rounding :func:`cluster_batch_merges` performs, so a key
    computed here matches the dispatch it predicts.  ``compaction`` and
    ``algorithm`` may be the user knobs (``"auto"``); the signature
    stores the *resolved* per-bucket values
    (:func:`repro.core.nnchain.resolve_batch_algorithm` with
    ``points_capable = points_dim > 0``).  An NN-chain bucket is
    canonicalized — full trip count, no threshold structure, baseline
    variant, no compaction — because the chain always runs the complete
    agglomeration and early stop is post-hoc: one executable per
    ``(bucket_n, bucket_B, method[, points_dim])`` regardless of the
    caller's early-stop knobs.
    """
    bn = bucket_n(n)
    algo = resolve_batch_algorithm(
        algorithm, method=method, engine=engine, bucket_n=bn,
        variant=variant, compaction=compaction,
        points_capable=points_dim > 0,
    )
    if algo == "nnchain":
        return BucketSignature(
            bucket_n=bn,
            bucket_B=bucket_batch(batch, b_multiple),
            method=method,
            engine="serial",
            variant="baseline",
            n_steps=bn - 1,
            with_threshold=False,
            compaction=False,
            algorithm="nnchain",
            points_dim=points_dim,
        )
    n_steps = max(bn - stop_at_k, 0)
    return BucketSignature(
        bucket_n=bn,
        bucket_B=bucket_batch(batch, b_multiple),
        method=method,
        engine=engine,
        variant=variant,
        n_steps=n_steps,
        with_threshold=with_threshold,
        compaction=_resolve_bucket_compaction(compaction, engine, bn, n_steps),
    )


@dataclass(frozen=True)
class BatchStats:
    """Scheduler accounting for one :func:`cluster_batch_merges` call."""

    n_problems: int
    buckets: tuple[tuple[int, int], ...]   # (bucket_n, n_problems) per bucket
    padded_problems: int                   # dead problems added for B rounding
    engine: str
    cells_real: int = 0                    # sum of n_b² (n_b·d matrix-free) real
    cells_padded: int = 0                  # sum of cells dispatched incl. padding
    # (bucket_n, 'lw' | 'nnchain') per dispatched bucket, aligned with
    # `buckets`; a ragged batch may mix engines across its buckets
    bucket_algorithms: tuple[tuple[int, str], ...] = ()

    @property
    def pad_waste(self) -> float:
        """Fraction of dispatched matrix cells that are padding (dead
        slots of real problems + whole dead problems)."""
        if self.cells_padded == 0:
            return 0.0
        return 1.0 - self.cells_real / self.cells_padded


# ---------------------------------------------------------------------------
# engines — one compiled program per (bucket_n, bucket_B, method, variant)
# ---------------------------------------------------------------------------


def _vmap_engine(Db, n_real, threshold, *, method, n_steps, variant,
                 with_threshold, compaction=False):
    """The shared batched composition: symmetrize + vmap of ``run_dense``.

    Finished problems simply churn garbage merge rows (their matrices go
    all-``+inf``) instead of paying a per-step ragged guard; the
    scheduler slices those rows off.  With a ``distance_threshold`` the
    loop is a ``while_loop`` whose vmap batching rule freezes finished
    lanes — an exhausted (all-inf) problem reads ``dmin = +inf`` and
    stops contributing work.  The threshold value is a traced operand
    (closed over, unbatched) so per-call radii share one compile.

    Compaction stage boundaries are bucket-wide: lanes merge in
    lockstep, so ONE gather pass per boundary re-packs every lane (a
    lane that ran out of live slots — ragged padding, threshold stop —
    is already below the bound and just compacts its survivors).
    """
    Db = symmetrize(Db)
    alive0 = jnp.arange(Db.shape[-1])[None, :] < n_real[:, None]

    def run(D, alive):
        return run_dense(
            D,
            alive,
            method=method,
            n_steps=n_steps,
            variant=variant,
            distance_threshold=threshold if with_threshold else None,
            compaction=compaction,
        )

    return jax.vmap(run)(Db, alive0)


@partial(
    jax.jit,
    static_argnames=("method", "n_steps", "variant", "with_threshold",
                     "compaction"),
)
def _run_vmap(Db, n_real, threshold, *, method, n_steps, variant,
              with_threshold, compaction=False):
    """Serial batched engine: the vmap composition on one device."""
    return _vmap_engine(Db, n_real, threshold, method=method,
                        n_steps=n_steps, variant=variant,
                        with_threshold=with_threshold, compaction=compaction)


@partial(
    jax.jit,
    static_argnames=("method", "n_steps", "mesh", "variant",
                     "with_threshold", "compaction"),
)
def _run_sharded(Db, n_real, threshold, *, method, n_steps, mesh, variant,
                 with_threshold, compaction=False):
    """Distributed batched engine: whole problems sharded over the mesh.

    Batch-axis ``shard_map`` — each device runs the same vmap
    composition on its local slice of problems; no collective is needed
    (the merge lists are per-problem, not replicated)."""

    def body(D_local, n_local, thr):
        return _vmap_engine(D_local, n_local, thr, method=method,
                            n_steps=n_steps, variant=variant,
                            with_threshold=with_threshold,
                            compaction=compaction)

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(AXIS, None, None), P(AXIS), P()),
        out_specs=LWResult(merges=P(AXIS, None, None), n_merges=P(AXIS)),
    )(Db, n_real, threshold)


def _run_kernel(Db, n_real, threshold, *, method, n_steps, variant,
                with_threshold, compaction=False):
    """Kernel batched engine: vmap of the Pallas composition."""
    from repro.kernels.ops import lance_williams_kernelized_batch

    return lance_williams_kernelized_batch(
        Db,
        n_real,
        method=method,
        n_steps=n_steps,
        variant=variant,
        distance_threshold=(
            float(threshold) if with_threshold else None
        ),
        compaction=compaction,
    )


# ---------------------------------------------------------------------------
# the bucketed scheduler
# ---------------------------------------------------------------------------


def _stack_bucket(mats: list[np.ndarray], n_pad: int, B_pad: int) -> np.ndarray:
    """One allocation: real problems in the first ``len(mats)`` slots,
    the rest all-zero (dead problems)."""
    out = np.zeros((B_pad, n_pad, n_pad), np.float32)
    for b, m in enumerate(mats):
        n = m.shape[0]
        out[b, :n, :n] = m
    return out


def pack_bucket(
    mats: list[np.ndarray], sig: BucketSignature
) -> tuple[np.ndarray, np.ndarray]:
    """Stack one bucket's problems into the engine's operand layout.

    Returns ``(Db, n_real)`` ready for the executable ``sig`` names:
    ``(bucket_B, bucket_n, bucket_n)`` stacked matrices (padded slots
    dead) and the ``(bucket_B,)`` int32 real-size vector.  Shared by the
    offline scheduler below and the service batcher, so the two dispatch
    paths cannot drift."""
    Db = _stack_bucket(mats, sig.bucket_n, sig.bucket_B)
    n_real = np.zeros((sig.bucket_B,), np.int32)
    n_real[: len(mats)] = [m.shape[0] for m in mats]
    return Db, n_real


def pack_points_bucket(
    points: list[np.ndarray], sig: BucketSignature
) -> tuple[np.ndarray, np.ndarray]:
    """Stack one matrix-free bucket's point sets into the engine layout.

    Returns ``(Xb, n_real)`` for the executable ``sig`` names:
    ``(bucket_B, bucket_n, points_dim)`` stacked points (padding rows
    are inert — padded slots are born dead in the engine) and the
    ``(bucket_B,)`` int32 real-size vector.  The matrix-free counterpart
    of :func:`pack_bucket`: a padded lane costs O(bucket_n · d) host
    memory instead of O(bucket_n²), which is the whole point of routing
    points traffic through the NN-chain bucket (DESIGN.md §11)."""
    Xb = np.zeros((sig.bucket_B, sig.bucket_n, sig.points_dim), np.float32)
    for b, X in enumerate(points):
        Xb[b, : X.shape[0]] = X
    n_real = np.zeros((sig.bucket_B,), np.int32)
    n_real[: len(points)] = [X.shape[0] for X in points]
    return Xb, n_real


def merge_prefix(n: int, stop_at_k: int, n_merges: int) -> int:
    """Rows of a padded slot's merge buffer that belong to the problem.

    A problem of ``n`` items stopping at ``k`` clusters owns the first
    ``max(n - stop_at_k, 0)`` trips; a threshold stop (or exhaustion
    under while-loop semantics) can cut that further via the recorded
    per-slot count.  The single source of the slicing rule for every
    bucket consumer."""
    return min(max(n - stop_at_k, 0), int(n_merges))


def cluster_batch_merges(
    matrices: list[np.ndarray],
    method: str = "complete",
    *,
    engine: str = "serial",
    mesh: Mesh | None = None,
    variant: str = "baseline",
    stop_at_k: int = 1,
    distance_threshold: float | None = None,
    compaction: bool | str = "auto",
    algorithm: str = "auto",
    points: list[np.ndarray | None] | None = None,
) -> tuple[list[np.ndarray], BatchStats]:
    """Cluster many independent ``(n_b, n_b)`` distance matrices at once.

    Returns ``(merge_lists, stats)`` — ``merge_lists[b]`` is the
    slot-convention merge list for problem ``b``, in input order:
    bit-identical to ``lance_williams(matrices[b], method, ...).merges``
    for the ``serial``/``distributed`` engines, index-identical with
    float-tolerance distances for ``kernel``.  With ``stop_at_k`` /
    ``distance_threshold`` each problem's list is the exact prefix the
    early-stopped single-problem run would produce (``stop_at_k``
    statically shrinks the bucket trip count by ``k - 1``).

    ``engine``: ``serial`` (vmap, one device), ``distributed`` (problems
    sharded over the mesh), or ``kernel`` (Pallas inner loops).

    ``algorithm`` routes each *bucket* through
    :func:`repro.core.nnchain.resolve_batch_algorithm` — ``"auto"``
    (default) keeps dense buckets on LW and sends matrix-free points
    buckets of ``NNCHAIN_BATCH_AUTO_MIN_N`` or larger to the batched
    NN-chain engine; ``"nnchain"`` forces the chain for every bucket
    (reducible methods, serial engine only).  NN-chain merge lists come
    back **canonicalized** (height-sorted, early stop applied post-hoc
    via :func:`repro.core.dendrogram.truncate_canonical`): same clusters
    and heights as LW to float tolerance, not bit-identical.

    ``points`` (optional, aligned with ``matrices``) marks matrix-free
    capable problems: entry ``b`` is the ``(n_b, d)`` float point set of
    problem ``b`` *under the squared-Euclidean convention of*
    :data:`repro.core.nnchain.POINTS_METHODS` — the caller asserts that
    convention by supplying it — and ``matrices[b]`` may then be
    ``None``.  A capable problem whose bucket routes to nnchain is
    dispatched matrix-free (the ``(n, n)`` matrix is never built, pad
    waste O(n·d)); one whose bucket stays on LW gets its matrix built
    here from the points.
    """
    if method not in METHODS:
        raise ValueError(f"unknown linkage method {method!r}")
    if engine not in ("serial", "distributed", "kernel"):
        raise ValueError(f"unknown batch engine {engine!r}")
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; pick from {VARIANTS}")
    if stop_at_k < 1:
        raise ValueError(f"stop_at_k must be >= 1, got {stop_at_k}")
    if algorithm == "nnchain":
        # validate method/engine once up front (raises on a bad combo)
        resolve_batch_algorithm(algorithm, method=method, engine=engine,
                                bucket_n=BUCKETS[0], variant=variant,
                                compaction=compaction)
    elif algorithm not in ("auto", "lw"):
        raise ValueError(
            f"algorithm must be 'auto', 'lw' or 'nnchain', got {algorithm!r}"
        )
    matrices = list(matrices)
    pts: list[np.ndarray | None] = (
        [None] * len(matrices) if points is None
        else [None if p is None else np.asarray(p, np.float32)
              for p in points]
    )
    if len(pts) != len(matrices):
        raise ValueError(
            f"points must align with matrices: {len(pts)} != {len(matrices)}"
        )
    sizes: list[int] = []
    for b in range(len(matrices)):
        p = pts[b]
        if p is not None:
            if p.ndim != 2:
                raise ValueError(
                    f"problem {b}: expected (n, d) points, got {p.shape}")
            if p.shape[0] < 2:
                raise ValueError(
                    f"problem {b}: need at least 2 items, got {p.shape[0]}")
            sizes.append(int(p.shape[0]))
            continue
        m = np.asarray(matrices[b])
        matrices[b] = m
        if m.ndim != 2 or m.shape[0] != m.shape[1]:
            raise ValueError(f"problem {b}: expected a square matrix, got {m.shape}")
        if m.shape[0] < 2:
            raise ValueError(f"problem {b}: need at least 2 items, got {m.shape[0]}")
        sizes.append(int(m.shape[0]))

    if engine == "distributed":
        from repro.core.distributed import flatten_mesh, make_cluster_mesh

        mesh = mesh if mesh is not None else make_cluster_mesh()
        if len(mesh.axis_names) != 1:
            mesh = flatten_mesh(mesh)
        b_multiple = mesh.devices.size
    else:
        b_multiple = 1

    # group problem indices by (shape bucket, matrix-free dim): a points
    # problem joins the matrix-free bucket only when its bucket resolves
    # to nnchain — otherwise its matrix is built and it rides the dense
    # bucket like any other problem
    groups: dict[tuple[int, int], list[int]] = {}
    for idx in range(len(matrices)):
        bn = bucket_n(sizes[idx])
        p = pts[idx]
        use_points = p is not None and resolve_batch_algorithm(
            algorithm, method=method, engine=engine, bucket_n=bn,
            variant=variant, compaction=compaction, points_capable=True,
        ) == "nnchain"
        if p is not None and not use_points and matrices[idx] is None:
            diff = p[:, None, :] - p[None, :, :]
            matrices[idx] = np.einsum("ijk,ijk->ij", diff, diff).astype(np.float32)
        groups.setdefault((bn, p.shape[1] if use_points else 0), []).append(idx)

    out: list[np.ndarray | None] = [None] * len(matrices)
    bucket_log: list[tuple[int, int]] = []
    algo_log: list[tuple[int, str]] = []
    padded_problems = 0
    cells_padded = 0
    cells_real = 0

    for n_pad, pdim in sorted(groups):
        idxs = groups[(n_pad, pdim)]
        bucket_log.append((n_pad, len(idxs)))
        sig = bucket_signature(
            n_pad,
            len(idxs),
            method=method,
            engine=engine,
            variant=variant,
            stop_at_k=stop_at_k,
            with_threshold=distance_threshold is not None,
            b_multiple=b_multiple,
            compaction=compaction,
            algorithm=algorithm,
            points_dim=pdim,
        )
        algo_log.append((n_pad, sig.algorithm))
        B_pad = sig.bucket_B
        padded_problems += B_pad - len(idxs)

        thr = jnp.float32(
            0.0 if distance_threshold is None else distance_threshold
        )

        if sig.algorithm == "nnchain":
            if pdim:
                cells_padded += B_pad * n_pad * pdim
                cells_real += sum(sizes[i] * pdim for i in idxs)
                Xb, n_real = pack_points_bucket([pts[i] for i in idxs], sig)
                res = _nnchain._run_points_batch(
                    Xb, n_real, thr, method=method, n_steps=sig.n_steps
                )
            else:
                cells_padded += B_pad * n_pad * n_pad
                cells_real += sum(sizes[i] ** 2 for i in idxs)
                Db, n_real = pack_bucket([matrices[i] for i in idxs], sig)
                res = _nnchain._run_batch(
                    Db, n_real, thr, method=method, n_steps=sig.n_steps
                )
            merges = np.asarray(res.merges)
            n_merges = np.asarray(res.n_merges)
            for slot, idx in enumerate(idxs):
                nr = sizes[idx]
                if int(n_merges[slot]) != nr - 1:
                    raise RuntimeError(
                        "NN-chain loop hit its iteration cap before "
                        "finishing — the input likely contains NaNs (the "
                        "chain invariant needs a total order on distances)"
                    )
                canon = dg.canonical_order(merges[slot, : nr - 1], n=nr)
                out[idx] = dg.truncate_canonical(
                    canon, nr, stop_at_k, distance_threshold
                )
            continue

        cells_padded += B_pad * n_pad * n_pad
        cells_real += sum(sizes[i] ** 2 for i in idxs)
        Db, n_real = pack_bucket([matrices[i] for i in idxs], sig)

        kwargs = dict(
            method=method,
            n_steps=sig.n_steps,
            variant=variant,
            with_threshold=sig.with_threshold,
            compaction=sig.compaction,
        )
        if engine == "serial":
            res = _run_vmap(Db, n_real, thr, **kwargs)
        elif engine == "kernel":
            res = _run_kernel(Db, n_real, thr, **kwargs)
        else:
            Dbj = jax.device_put(
                jnp.asarray(Db), NamedSharding(mesh, P(AXIS, None, None))
            )
            nrj = jax.device_put(
                jnp.asarray(n_real), NamedSharding(mesh, P(AXIS))
            )
            res = _run_sharded(Dbj, nrj, thr, mesh=mesh, **kwargs)
        merges = np.asarray(res.merges)
        n_merges = np.asarray(res.n_merges)
        for slot, idx in enumerate(idxs):
            upto = merge_prefix(int(n_real[slot]), stop_at_k, n_merges[slot])
            out[idx] = merges[slot, :upto]

    stats = BatchStats(
        n_problems=len(matrices),
        buckets=tuple(bucket_log),
        padded_problems=padded_problems,
        engine=engine,
        cells_real=cells_real,
        cells_padded=cells_padded,
        bucket_algorithms=tuple(algo_log),
    )
    assert all(m is not None for m in out)
    return out, stats  # type: ignore[return-value]
