"""Public clustering API — the framework's first-class entry point.

``cluster(...)`` accepts either raw points (``(n, d)`` embeddings or
``(n, atoms, 3)`` conformations) or a pre-built ``(n, n)`` distance matrix,
picks an algorithm (the O(n³)-work Lance-Williams merge loop or the
O(n²) NN-chain engine) and an execution backend (serial / distributed /
Pallas-kernel inner loops), and returns a :class:`ClusterResult` with
the merge list, a scipy-style linkage matrix and a label extractor —
the paper's dendrogram, cut at any level.

The docstring of :func:`cluster` is the single reference for how the
engine knobs (``algorithm`` / ``backend`` / ``variant`` /
``compaction`` / ``stop_at_k`` / ``distance_threshold`` /
``matrix_free``) compose; the per-backend entry points
(:func:`repro.core.lance_williams.lance_williams`,
:func:`repro.kernels.ops.lance_williams_kernelized`,
:func:`repro.core.nnchain.nn_chain`, …) defer here rather than
re-documenting the matrix.
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Literal, Sequence

import jax
import numpy as np

from repro.core import dendrogram as dg
from repro.core.batched import BatchStats, bucket_n, cluster_batch_merges
from repro.core.distance import pairwise_euclidean, pairwise_rmsd, pairwise_sq_euclidean
from repro.core.lance_williams import lance_williams
from repro.core.linkage import METHODS, default_metric
from repro.core.nnchain import (
    POINTS_METHODS,
    nn_chain,
    nn_chain_from_points,
    points_stage_plan,
    resolve_algorithm,
    resolve_batch_algorithm,
    resolve_matrix_free,
)
from repro.obs import get_registry, phase

Backend = Literal["auto", "serial", "distributed", "kernel"]
Algorithm = Literal["auto", "lw", "nnchain", "twophase", "landmark"]


@dataclass
class ClusterResult:
    merges: np.ndarray                 # (n_merges, 4) slot-convention merge list
    method: str
    backend: str
    algorithm: str = "lw"              # merge engine: "lw" | "nnchain"
    n_leaves: int | None = None        # explicit n for early-stopped runs
    # original points, when the input was points (enables centroids/assign)
    points: np.ndarray | None = field(default=None, repr=False)
    # the (n, n) matrix the tree was built on (enables exemplars)
    distances: np.ndarray | None = field(default=None, repr=False)
    metric: str | None = None          # metric used to embed points (None: raw matrix)
    # trips of the one NN-chain loop that built the tree (its ``iters``),
    # the stages that loop ran in (1: it never compacted) and the shards
    # it ran over (1 but on the sharded chain); None for the LW engines,
    # the batched and the approximate tiers
    chain_trips: int | None = None
    chain_stages: int | None = None
    chain_shards: int | None = None
    linkage_matrix: np.ndarray = field(init=False, repr=False)

    def __post_init__(self) -> None:
        if self.n_leaves is None:
            self.n_leaves = self.merges.shape[0] + 1
        self.linkage_matrix = dg.to_linkage_matrix(self.merges, n=self.n_leaves)

    @property
    def n(self) -> int:
        return int(self.n_leaves)

    @property
    def n_merges(self) -> int:
        return int(self.merges.shape[0])

    def labels(self, k: int) -> np.ndarray:
        """Flat labels for ``k`` clusters (cut the dendrogram at level k).

        An early-stopped run only holds ``n_merges`` merges, so ``k``
        must be at least ``n - n_merges`` (the stop level).
        """
        return dg.cut(self.merges, k, n=self.n)

    def heights(self) -> np.ndarray:
        return dg.merge_heights(self.merges)

    def _distance_matrix(self) -> np.ndarray:
        # exemplars are medoids of the matrix the TREE saw, so raw stored
        # input must pass through the same normalization every engine
        # applies (mirror a triangle / average an asymmetric square, zero
        # the diagonal) before any row sums are taken
        from repro.core.engine import symmetrize

        if self.distances is not None:
            return np.asarray(symmetrize(self.distances))
        if self.points is not None:
            metric = self.metric or default_metric(self.method)
            return np.asarray(symmetrize(build_distance_matrix(self.points, metric)))
        raise ValueError(
            "this ClusterResult kept neither points nor distances; build it "
            "through cluster()/cluster_batch()/the service, or call "
            "repro.core.dendrogram.cut_exemplars with your own matrix"
        )

    def exemplars(self, k: int) -> np.ndarray:
        """Medoid leaf index per cluster of the ``k``-cut.

        ``exemplars(k)[c]`` is the leaf whose summed distance to the rest
        of cluster ``c`` is minimal — the per-cluster representative the
        streaming-assignment service exports
        (:mod:`repro.service.assign`): new points are labeled by one
        distance call against ``k`` exemplars instead of a re-cluster.
        """
        _, ex = dg.cut_exemplars(self.merges, k, self._distance_matrix(), n=self.n)
        return ex

    def centroids(self, k: int) -> np.ndarray:
        """Per-cluster mean of the stored input points at the ``k``-cut."""
        if self.points is None or np.asarray(self.points).ndim != 2:
            raise ValueError(
                "centroids need the original (n, d) points — cluster points "
                "(not a distance matrix) or use exemplars(k) instead"
            )
        X = np.asarray(self.points)
        labels = self.labels(k)
        return np.stack([X[labels == c].mean(axis=0) for c in range(k)])


def build_distance_matrix(X, metric: str = "euclidean") -> jax.Array:
    X = np.asarray(X)
    if metric == "rmsd":
        if X.ndim != 3 or X.shape[-1] != 3:
            raise ValueError("rmsd metric expects (n, atoms, 3) conformations")
        return pairwise_rmsd(X)
    if X.ndim != 2:
        raise ValueError(f"expected (n, d) points, got {X.shape}")
    if metric == "euclidean":
        return pairwise_euclidean(X)
    if metric == "sqeuclidean":
        return pairwise_sq_euclidean(X)
    raise ValueError(f"unknown metric {metric!r}")


def _interpret_input(data, method: str, metric: str | None,
                     is_distance: bool | None = None, *,
                     materialize: bool = True):
    """Shared input interpretation for ``cluster``, ``cluster_batch`` and
    the service batcher: a square 2-D array with ``metric is None`` is
    treated as a pre-built distance matrix; anything else is points
    embedded via *metric*, defaulting to
    :func:`repro.core.linkage.default_metric` (scipy convention).

    The square-with-no-metric case is ambiguous — ``(n, n)`` *points* in
    ``n`` dimensions look exactly like a distance matrix.  ``is_distance``
    disambiguates explicitly (the cheap check service callers should
    use); when it is left ``None`` and the ambiguous interpretation
    fires on a non-symmetric array, a ``UserWarning`` flags the likely
    mistake (the engine would silently symmetrize it by averaging).

    Returns ``(D, points, metric_used)`` — ``points``/``metric_used`` are
    ``None`` for matrix input.  ``D`` may be a jax array (built matrices
    stay on device for the single-problem engines); batch callers convert
    to numpy for host-side bucket stacking.  With ``materialize=False``
    the classification runs but the O(n²) matrix build for points input
    is *deferred* (``D`` comes back ``None``) — the matrix-free NN-chain
    path must decide before any ``(n, n)`` array exists."""
    arr = np.asarray(data)
    looks_square = arr.ndim == 2 and arr.shape[0] == arr.shape[1]
    if is_distance is None:
        is_distance = metric is None and looks_square
        # valid matrix forms stay silent: symmetric, or upper-triangle-only
        # (engine.symmetrize mirrors the triangle — a documented input)
        plausible_matrix = is_distance and (
            arr.shape[0] <= 1
            or np.allclose(arr, arr.T, rtol=1e-5, atol=1e-6)
            or not np.any(np.tril(arr, k=-1))
        )
        if is_distance and not plausible_matrix:
            warnings.warn(
                "square (n, n) input with metric=None is interpreted as a "
                "pre-built distance matrix, but this one is not symmetric "
                "(the engine symmetrizes by averaging D and D.T). If it is "
                "actually n points in n dimensions, pass is_distance=False "
                "or an explicit metric; pass is_distance=True to silence "
                "this warning.",
                UserWarning,
                stacklevel=3,
            )
    if is_distance:
        if metric is not None:
            raise ValueError(
                f"is_distance=True conflicts with metric={metric!r}: a "
                "pre-built distance matrix needs no embedding metric"
            )
        if not looks_square:
            raise ValueError(
                f"is_distance=True requires a square (n, n) matrix, got {arr.shape}"
            )
        return arr, None, None
    if metric is None:
        metric = default_metric(method)
    if not materialize:
        return None, arr, metric
    return build_distance_matrix(arr, metric), arr, metric


def _choose_engine(
    D,
    points: np.ndarray | None,
    used_metric: str | None,
    n: int,
    method: str,
    *,
    algorithm: str,
    backend: str,
    mesh,
    variant: str,
    stop_at_k: int,
    distance_threshold: float | None,
    compaction: bool | str,
    matrix_free: bool | str,
    n_landmarks: int | None,
    seed: int,
    refine: int,
) -> tuple[str, str, Callable[[], tuple], int]:
    """Resolve :func:`cluster`'s knobs (its docstring is the reference).

    Returns ``(algorithm, backend, launch, stages)``: the resolved
    engine, a call that starts it and returns ``(result, D)`` — the
    engine's ``LWResult``-like result, possibly still computing on the
    device, and the distance matrix the tree is built on (``None`` where
    none exists) — and the stages a chain engine's loop runs in, known
    from its static plan (1 unless the serial matrix-free chain stages).
    Every contradiction among the knobs raises here, before any engine
    runs.
    """
    if matrix_free not in (True, False, None, "auto"):
        # validate up front — the LW branch never consults matrix_free, so
        # without this a typo'd value would only error once n grows past
        # the nnchain auto threshold
        raise ValueError(
            f"matrix_free must be a bool or 'auto', got {matrix_free!r}"
        )
    if matrix_free not in (None, "auto"):
        matrix_free = bool(matrix_free)   # membership passed 0/1: same as bool
    if matrix_free is True:
        # matrix-free is an nnchain-family capability: an explicit request
        # makes "auto" mean nnchain, and an explicit "lw" is a
        # contradiction — never silently build the (n, n) matrix the
        # caller opted out of.  An explicit nnchain/twophase/landmark
        # already names a matrix-free-capable engine and stands.
        if algorithm == "lw":
            raise ValueError(
                "matrix_free=True requires the NN-chain engine, but "
                "algorithm='lw' pins the Lance-Williams loop (every LW "
                "backend stores the dense matrix)"
            )
        if algorithm == "auto":
            algorithm = "nnchain"

    if n_landmarks is not None or refine != 0:
        # the landmark knobs name the landmark tier, the same way
        # matrix_free=True names the nnchain family: an explicit request
        # makes "auto" mean landmark, any other explicit algorithm is a
        # contradiction
        if algorithm == "auto":
            algorithm = "landmark"
        elif algorithm != "landmark":
            raise ValueError(
                f"n_landmarks/refine belong to the landmark tier, but "
                f"algorithm={algorithm!r} pins a different engine"
            )

    if backend == "auto":
        # an explicit nnchain/twophase request owns the backend choice:
        # their default composition is the serial chain, so "auto" must
        # not hand them a multi-device mesh they did not ask for (the
        # sharded chain is explicit backend="distributed" opt-in)
        backend = (
            "serial" if algorithm in ("nnchain", "twophase", "landmark")
            else "distributed" if len(jax.devices()) > 1
            else "serial"
        )

    points_capable = (
        points is not None and points.ndim == 2
        and method in POINTS_METHODS and used_metric == "sqeuclidean"
    )

    if algorithm == "landmark":
        from repro.core.landmark import LANDMARK_METRICS, landmark_cluster

        if points is None:
            raise ValueError(
                "algorithm='landmark' samples landmarks from coordinates "
                "and assigns the rest through the streaming labeler: it "
                "needs (n, d) points or (n, atoms, 3) conformations, not "
                "a pre-built distance matrix (which already paid the "
                "Ω(n²) evaluations this tier exists to avoid)"
            )
        if used_metric not in LANDMARK_METRICS:
            raise ValueError(
                f"algorithm='landmark' supports metrics {LANDMARK_METRICS} "
                f"(the assignment labeler's), got {used_metric!r}"
            )
        if backend != "serial":
            raise ValueError(
                f"algorithm='landmark' is single-device (the whole point "
                f"is that n·k work fits one host), got backend={backend!r}"
            )
        return algorithm, backend, lambda: (landmark_cluster(
            points, method, metric=used_metric,
            n_landmarks=n_landmarks, seed=seed, refine=refine,
        ), None), 1

    if algorithm == "twophase":
        if not points_capable:
            raise ValueError(
                "algorithm='twophase' shards points and agglomerates "
                "geometric summaries: it needs (n, d) points input and a "
                f"method from {POINTS_METHODS} under the squared-"
                f"Euclidean convention; got method={method!r}, "
                f"metric={used_metric!r}, "
                f"input shape {None if points is None else points.shape}"
            )
        if backend not in ("serial", "distributed"):
            raise ValueError(
                f"algorithm='twophase' supports backend='serial'/"
                f"'distributed', got {backend!r}"
            )
        from repro.core.distributed import two_phase_from_points

        return algorithm, backend, lambda: (
            two_phase_from_points(points, method), None), 1

    algorithm = resolve_algorithm(
        algorithm, method=method, backend=backend, n=n,
        variant=variant, compaction=compaction,
    )

    def on_matrix(engine: Callable) -> Callable[[], tuple]:
        # the dense engines run on the input matrix, or on one built from
        # the points when the engine starts
        def launch() -> tuple:
            D_ = D if points is None else build_distance_matrix(points,
                                                                used_metric)
            return engine(D_), D_
        return launch

    if algorithm == "nnchain":
        if backend == "distributed":
            # the sharded matrix-free chain (DESIGN.md §12) is the ONLY
            # distributed chain composition — it needs the points
            # capability, and matrix_free=False contradicts it
            if matrix_free is False or not points_capable:
                raise ValueError(
                    "backend='distributed' with algorithm='nnchain' is "
                    "the sharded matrix-free chain: it needs (n, d) "
                    f"points input, a method from {POINTS_METHODS} under "
                    "the squared-Euclidean convention, and matrix_free "
                    f"left on (got method={method!r}, "
                    f"metric={used_metric!r}, matrix_free={matrix_free!r}, "
                    f"input shape "
                    f"{None if points is None else points.shape}) — use "
                    "algorithm='lw' for the dense row-sharded engine"
                )
            from repro.core.distributed import (
                distributed_nn_chain_from_points,
            )

            return algorithm, backend, lambda: (
                distributed_nn_chain_from_points(points, method, mesh=mesh),
                None), 1
        use_points = resolve_matrix_free(
            matrix_free,
            points_shape=None if points is None else points.shape,
            method=method, metric=used_metric, n=n,
        )
        if use_points:
            # the (n, n) matrix is never materialized — keep it that way
            return algorithm, "serial", lambda: (
                nn_chain_from_points(points, method), None), len(
                points_stage_plan(n, points.shape[1], n - 1))
        return (algorithm, "serial",
                on_matrix(lambda D_: nn_chain(D_, method)), 1)

    if backend == "serial":
        engine = lance_williams
    elif backend == "distributed":
        from repro.core.distributed import distributed_lance_williams

        engine = partial(distributed_lance_williams, mesh=mesh)
    elif backend == "kernel":
        from repro.kernels.ops import lance_williams_kernelized

        def engine(D_, **kw):
            return lance_williams_kernelized(jax.numpy.asarray(D_), **kw)
    else:
        raise ValueError(f"unknown backend {backend!r}")
    return algorithm, backend, on_matrix(lambda D_: engine(
        D_, method=method, variant=variant, stop_at_k=stop_at_k,
        distance_threshold=distance_threshold, compaction=compaction)), 1


def cluster(
    data,
    method: str = "complete",
    *,
    metric: str | None = None,
    is_distance: bool | None = None,
    algorithm: Algorithm = "auto",
    backend: Backend = "auto",
    mesh=None,
    variant: str = "baseline",
    stop_at_k: int = 1,
    distance_threshold: float | None = None,
    compaction: bool | str = "auto",
    matrix_free: bool | str = "auto",
    keep_inputs: bool = True,
    n_landmarks: int | None = None,
    seed: int = 0,
    refine: int = 0,
) -> ClusterResult:
    """Hierarchically cluster *data* — THE reference for the engine knobs.

    Every entry point (this function, :func:`cluster_batch`, the service,
    and the per-backend functions they wrap) takes some subset of the
    knobs below; this docstring is the one place their interactions are
    specified.

    **Input** — ``data`` is an ``(n, n)`` distance matrix when square and
    ``metric is None``, else ``(n, d)`` points / ``(n, atoms, 3)``
    conformations embedded via ``metric`` (default:
    :func:`repro.core.linkage.default_metric` — squared Euclidean for
    the geometric methods, plain Euclidean otherwise, scipy's
    convention).  ``is_distance=True/False`` disambiguates the square
    points-vs-matrix case explicitly; leaving it ``None`` keeps the
    shape heuristic, which warns on a non-symmetric square array.

    **algorithm** — which merge engine computes the dendrogram:

    * ``"lw"``: the paper's Lance-Williams merge loop
      (:mod:`repro.core.engine`) — O(n²) work *per merge*; the only
      engine for centroid/median (non-reducible) and the only one the
      ``backend``/``variant``/``compaction`` execution knobs apply to.
    * ``"nnchain"``: the nearest-neighbor-chain engine
      (:mod:`repro.core.nnchain`, DESIGN.md §11) — exact for the
      reducible methods (single/complete/average/weighted/ward) at
      O(n²) *total* work.  Single-device; merges are canonicalized to
      height order (:func:`repro.core.dendrogram.canonical_order`), so
      the result matches the LW engine's on tie-free input.
    * ``"twophase"``: the explicitly **approximate** distributed tier
      (:func:`repro.core.distributed.two_phase_from_points`): shard the
      points into contiguous blocks, chain-cluster each block locally,
      truncate at an intermediate level, agglomerate the surviving
      geometric summaries globally.  Points input with a
      :data:`repro.core.nnchain.POINTS_METHODS` method under its
      squared-Euclidean convention only.  No merge can cross shards
      below the truncation level — the dendrogram-quality delta is
      *measured* (merge-set agreement, EXPERIMENTS.md §Perf-7), not
      assumed; reach for it only when the exact engines' per-step
      collectives are the bottleneck.
    * ``"landmark"``: the **sub-quadratic** approximate tier
      (:func:`repro.core.landmark.landmark_cluster`, DESIGN.md §15) —
      ``k`` seeded landmarks (``n_landmarks`` / ``seed``; default
      ``⌈√n·log₂ n⌉``) clustered exactly by the NN-chain engine, the
      remaining ``n−k`` objects assigned through the streaming labeler,
      optional ``refine`` centroid passes.  O(n·k + k²) distance
      *evaluations* instead of Ω(n²) — the only tier that changes the
      query complexity, not just its constant — with the quality delta
      measured by the ``cut_label_agreement``/ARI gates
      (EXPERIMENTS.md §Perf-10).  Points/conformations input with a
      reducible method under an
      :data:`repro.core.landmark.LANDMARK_METRICS` metric; serial
      backend only.
    * ``"auto"`` (default): nnchain for large reducible problems on the
      serial path (``n ≥`` :data:`repro.core.nnchain.NNCHAIN_AUTO_MIN_N`
      with default ``variant``/``compaction``), LW otherwise — the
      distributed/kernel backends always keep LW under ``auto``
      (the sharded chain is explicit opt-in), and batched/service
      traffic keeps LW for dense buckets while routing *matrix-free*
      points buckets of at least
      :data:`repro.core.nnchain.NNCHAIN_BATCH_AUTO_MIN_N` to the batched
      chain (see :func:`cluster_batch`).  Caveat: on input with *exactly tied* distances (common
      for quantized or duplicated embeddings) the two engines may break
      ties differently and return a different — equally valid —
      dendrogram; pin ``algorithm="lw"`` where bit-compatibility with
      the LW loop's row-major tie-breaking matters.

    **backend** — execution wrapper: ``serial`` (one device),
    ``distributed`` (over the mesh), ``kernel`` (Pallas inner ops, LW
    only), ``auto`` (distributed iff >1 device for LW; serial for an
    explicit nnchain/twophase).  ``backend="distributed"`` composes with
    both algorithms: LW runs the paper's row-sharded merge loop on the
    dense matrix (O(n²/p) per device); nnchain runs the **sharded
    matrix-free chain**
    (:func:`repro.core.distributed.distributed_nn_chain_from_points`,
    DESIGN.md §12) — ``(n, d)`` points block-row sharded, O(n·d/p + n)
    per device, three O(d)/O(p) collectives per chain trip, merges
    identical to the serial chain.  The sharded chain *requires* the
    matrix-free capability (points input, geometric-summary method,
    squared-Euclidean metric); ``matrix_free=False`` contradicts it and
    raises.

    **variant** (LW only) — argmin primitive on any backend:
    ``baseline`` (full masked scan), ``rowmin`` (cached row minima),
    ``lazy`` (cached minima + bounded dirty-row drain).  Bit-identical
    outputs; pick on measured speed.

    **compaction** (LW only, any backend) — stage schedule (DESIGN.md
    §3): pack live rows into a half-size matrix each time the live count
    halves; merges unchanged, dense work ~0.57×.  ``"auto"`` (default)
    stages whenever the plan has >1 stage.  The knob is ignored by the
    nnchain engine, and an *explicitly* set value steers
    ``algorithm="auto"`` back to LW (the knob names an LW execution
    schedule).  The serial matrix-free chain compacts its dead summaries
    on its own schedule, with no knob (DESIGN.md §11): its row reads all
    n summaries, so it stages whenever the summaries are large enough to
    pay for the gather; the dense chain's O(n) row is not worth a gather
    of its (n, n) matrix.

    **stop_at_k / distance_threshold** (any algorithm, any backend) —
    early termination, composable: stop at ``k`` remaining clusters
    and/or before the first merge above the threshold.  On LW these
    genuinely shorten the loop (static trip shrink / while-loop exit);
    on nnchain the full agglomeration is O(n²) anyway, so the engine
    runs it and truncates the canonical prefix — the same prefix
    contract either way, and ``labels(k)`` works down to the stop
    level.  One boundary caveat: the engines' heights agree only to
    float tolerance, so a ``distance_threshold`` sitting *exactly on* a
    merge height may include/exclude that borderline merge differently
    across algorithms — thresholds between merge heights behave
    identically.

    **matrix_free** (nnchain capability) — ``"auto"`` (default) drops
    the ``(n, n)`` matrix entirely for large ``(n, d)`` points input
    with a geometric-summary method (ward by default; average/weighted
    under an explicit ``metric="sqeuclidean"``), keeping peak memory
    O(n·d + n); ``True`` forces it — ``algorithm="auto"`` then resolves
    to nnchain regardless of size, ``algorithm="lw"`` is an error, and
    an input/method that cannot support it raises rather than silently
    building the matrix; ``False`` pins the dense chain loop.  A
    matrix-free result stores
    no ``distances`` (``exemplars()`` would rebuild O(n²) on the host —
    it stays available, just not free).

    **n_landmarks / seed / refine** (landmark only) — landmark count
    (default ``⌈√n·log₂ n⌉``), sampling seed (same seed ⇒ bit-identical
    run), and bounded centroid-refinement passes (Euclidean metrics).
    An explicit ``n_landmarks``/``refine`` resolves ``algorithm="auto"``
    to the landmark tier and contradicts any other explicit engine.

    **keep_inputs** — store the input points/distance matrix on the
    result (enables ``exemplars``/``centroids`` and the
    streaming-assignment export).  Pass ``False`` when accumulating many
    results; the pinned ``(n, n)`` matrix is O(n²) per result.

    Each call times its phases as ``repro/cluster/*`` spans on the
    profiler's clock and the process-global metrics registry, and counts
    a chain engine's loop trips, stages and shards (DESIGN.md §13).
    """
    with phase("cluster"):
        with phase("cluster/input"):
            if method not in METHODS:
                raise ValueError(f"unknown linkage method {method!r}")
            D, points, used_metric = _interpret_input(
                data, method, metric, is_distance, materialize=False
            )
            n = int((D if points is None else points).shape[0])
            algorithm, backend, launch, stages = _choose_engine(
                D, points, used_metric, n, method,
                algorithm=algorithm, backend=backend, mesh=mesh,
                variant=variant, stop_at_k=stop_at_k,
                distance_threshold=distance_threshold, compaction=compaction,
                matrix_free=matrix_free, n_landmarks=n_landmarks, seed=seed,
                refine=refine,
            )
        with phase("cluster/engine"):
            res, D = launch()
            jax.block_until_ready(res)
        with phase("cluster/fetch"):
            merges, n_merges, iters = jax.device_get(
                (res.merges, res.n_merges, getattr(res, "iters", None))
            )
        chain_trips = chain_stages = chain_shards = None
        if iters is not None:
            chain_trips, chain_stages = int(iters), stages
            chain_shards = getattr(res, "shards", 1)
            registry = get_registry()
            registry.histogram(
                "chain_trips", "NN-chain loop trips per chain call"
            ).observe(chain_trips)
            registry.histogram(
                "chain_stages", "stages of the NN-chain loop per chain call"
            ).observe(chain_stages)
            registry.histogram(
                "chain_shards", "mesh size of the NN-chain loop per chain call"
            ).observe(chain_shards)

        if algorithm == "lw":
            merges = merges[: int(n_merges)]
        else:
            if algorithm == "nnchain":
                if n > 1 and int(n_merges) != n - 1:
                    raise RuntimeError(
                        "NN-chain loop hit its iteration cap before "
                        "finishing — the input likely contains NaNs (the "
                        "chain invariant needs a total order on distances)"
                    )
                with phase("cluster/canonical_order"):
                    merges = dg.canonical_order(merges, n=n)
            # the landmark and two-phase tiers return heights already
            # monotone-repaired and canonical: only truncate
            with phase("cluster/truncate"):
                merges = dg.truncate_canonical(
                    merges, n, stop_at_k, distance_threshold
                )

        with phase("cluster/result"):
            return ClusterResult(
                merges=merges,
                method=method,
                backend=backend,
                algorithm=algorithm,
                n_leaves=n,
                points=points if keep_inputs else None,
                distances=D if (keep_inputs and D is not None) else None,
                metric=used_metric,
                chain_trips=chain_trips,
                chain_stages=chain_stages,
                chain_shards=chain_shards,
            )


@dataclass
class BatchResult(Sequence):
    """Results of a :func:`cluster_batch` call — one dendrogram per problem.

    Sequence of :class:`ClusterResult` in input order, plus the scheduler's
    :class:`~repro.core.batched.BatchStats` (shape buckets touched, padding
    waste, engine used).
    """

    results: list[ClusterResult]
    stats: BatchStats

    def __len__(self) -> int:
        return len(self.results)

    def __getitem__(self, idx):
        return self.results[idx]

    def labels(self, k: int) -> list[np.ndarray]:
        """Per-problem flat labels for ``k`` clusters.

        ``k`` is clamped per problem to ``[1, n_b]`` (small problems
        saturate at one-item clusters) and, for an early-stopped batch,
        up to the stop level ``n_b - n_merges_b`` (the coarsest cut the
        recorded prefix supports); ``k <= 0`` is a hard error — there is
        no such thing as a non-positive cluster count.
        """
        if k <= 0:
            raise ValueError(f"k must be a positive cluster count, got {k}")
        return [
            r.labels(max(1, min(k, r.n), r.n - r.n_merges))
            for r in self.results
        ]


def cluster_batch(
    problems: Sequence,
    method: str = "complete",
    *,
    metric: str | None = None,
    is_distance: bool | None = None,
    algorithm: Algorithm = "auto",
    backend: Backend = "auto",
    mesh=None,
    variant: str = "baseline",
    stop_at_k: int = 1,
    distance_threshold: float | None = None,
    compaction: bool | str = "auto",
    keep_inputs: bool = False,
) -> BatchResult:
    """Cluster MANY independent problems in one compiled program each bucket.

    ``problems`` is a sequence of independent inputs, each interpreted
    exactly as :func:`cluster` interprets its ``data`` argument: an
    ``(n, n)`` distance matrix when square and ``metric is None``, else
    ``(n, d)`` points / ``(n, atoms, 3)`` conformations with a metric
    (``is_distance`` forces one reading for every problem).
    Problem sizes may be ragged — the scheduler pads them into shape
    buckets (DESIGN.md §9) and runs one batched engine call per bucket.

    backend: ``serial`` (vmap over problems on one device), ``distributed``
    (whole problems sharded across mesh devices — *inter*-problem
    parallelism, zero communication), ``kernel`` (Pallas inner loops under
    the vmap batching rule), or ``auto`` (distributed iff >1 device).

    For the ``serial`` and ``distributed`` backends every problem's merge
    list is bit-identical to what the single-problem
    ``cluster(problems[b], method, backend='serial', ...)`` returns; the
    ``kernel`` backend matches merge *indices* exactly with merge
    distances equal to float tolerance (same contract as the
    single-problem kernel backend).  ``variant`` and the early-stop knobs
    apply per problem; ``compaction`` resolves per *bucket* (lockstep
    lanes share each stage boundary) and never changes any problem's
    merge list.

    ``keep_inputs=True`` stores each problem's points/distance matrix on
    its :class:`ClusterResult` (required for ``exemplars``/``centroids``
    and the streaming-assignment export).  Off by default: a large batch
    would otherwise pin O(Σ n_b²) matrix memory for the life of the
    result list.

    ``algorithm`` picks the merge engine per shape *bucket* (engines:
    see :func:`cluster`; routing:
    :func:`repro.core.nnchain.resolve_batch_algorithm`).  ``"auto"``
    (default) keeps dense buckets on LW — lockstep lanes are the LW
    loop's regime, and the vmapped chain loop's per-lane gathers erase
    its asymptotic edge on dense buckets — but routes *matrix-free*
    buckets (``(n, d)`` points input under the squared-Euclidean
    convention: ward by default, average/weighted with an explicit
    ``metric="sqeuclidean"``) of at least
    :data:`repro.core.nnchain.NNCHAIN_BATCH_AUTO_MIN_N` to the batched
    NN-chain engine, which never builds the ``(n, n)`` matrices and pads
    O(n·d) instead of O(n²) per lane.  ``"nnchain"`` forces the chain
    for every bucket (reducible methods, serial backend only); ``"lw"``
    pins the LW loop everywhere.  NN-chain merge lists come back
    height-sorted (:func:`repro.core.dendrogram.canonical_order`) —
    same dendrogram as LW to float tolerance on tie-free input, not
    bit-identical — so pin ``algorithm="lw"`` where bit-identity with
    the single-problem LW runs matters.
    """
    if method not in METHODS:
        raise ValueError(f"unknown linkage method {method!r}")
    if backend == "auto":
        # an explicit nnchain request owns the backend choice (it is a
        # single-device engine) — same rule as cluster()
        backend = (
            "serial" if algorithm == "nnchain"
            else "distributed" if len(jax.devices()) > 1
            else "serial"
        )
    if backend not in ("serial", "distributed", "kernel"):
        raise ValueError(f"unknown backend {backend!r}")

    interps = [
        _interpret_input(data, method, metric, is_distance, materialize=False)
        for data in problems
    ]
    # Per problem: matrix-free capable iff the points mode's geometric
    # summaries apply (same capability rule as cluster()'s matrix_free).
    # A capable problem whose bucket resolves to nnchain ships points and
    # never builds its matrix; everything else builds the dense matrix
    # here (points input embeds via its metric, exactly as before).
    matrices: list[np.ndarray | None] = []
    points_list: list[np.ndarray | None] = []
    algos: list[str] = []
    sizes: list[int] = []
    for D, pts, used_metric in interps:
        n_b = int((D if pts is None else pts).shape[0])
        sizes.append(n_b)
        capable = (
            pts is not None and pts.ndim == 2
            and method in POINTS_METHODS and used_metric == "sqeuclidean"
        )
        algo_b = resolve_batch_algorithm(
            algorithm, method=method, engine=backend,
            bucket_n=bucket_n(max(n_b, 2)), variant=variant,
            compaction=compaction, points_capable=capable,
        )
        algos.append(algo_b)
        if algo_b == "nnchain" and capable:
            matrices.append(None)
            points_list.append(np.asarray(pts, np.float32))
        else:
            matrices.append(
                np.asarray(D if pts is None
                           else build_distance_matrix(pts, used_metric))
            )
            points_list.append(None)

    merge_lists, stats = cluster_batch_merges(
        matrices,
        method,
        engine=backend,
        mesh=mesh,
        variant=variant,
        stop_at_k=stop_at_k,
        distance_threshold=distance_threshold,
        compaction=compaction,
        algorithm=algorithm,
        points=points_list,
    )
    results = [
        ClusterResult(
            merges=np.asarray(m),
            method=method,
            backend=backend,
            algorithm=algo,
            n_leaves=n_b,
            points=pts if keep_inputs else None,
            distances=mat if (keep_inputs and mat is not None) else None,
            metric=used_metric,
        )
        for m, mat, algo, n_b, (_, pts, used_metric)
        in zip(merge_lists, matrices, algos, sizes, interps)
    ]
    return BatchResult(results=results, stats=stats)
