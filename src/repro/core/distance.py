"""Distance-matrix builders (the paper's "parallelized RMSD" phase).

The paper's input is an ``n × n`` distance matrix; for its motivating
application the matrix holds pairwise RMSD between candidate protein
conformations, computed in parallel before clustering starts.  This module
provides the matrix builders:

* ``pairwise_sq_euclidean`` / ``pairwise_euclidean`` / ``pairwise_cosine``
  — Gram-matrix form ``‖x‖² + ‖y‖² − 2·x·yᵀ`` so the heavy lifting is a
  single MXU matmul (the Pallas ``pairwise`` kernel is the tiled version).
* ``pairwise_rmsd`` — optimal-superposition RMSD via the Kabsch algorithm
  (vmapped 3×3 cross-covariances and their singular values by a few
  unrolled Jacobi sweeps — elementwise work only).

All builders are jit-friendly and batch over the full pair grid.

**Distance-query accounting.**  The sub-quadratic landmark tier
(DESIGN.md §15) claims O(n·k + k²) distance *evaluations* instead of the
Ω(n²) every dense path pays — a claim that must be measured, not
assumed.  :func:`count_distance_queries` opens a :class:`DistanceBudget`
scope; inside it every builder in this module (and the row-build
dispatch in :mod:`repro.kernels.pairwise`) records how many pairwise
distances its call evaluates.  Recording is **host-side only**: a call
made while jax is tracing (arguments are tracers) is skipped, because a
traced call executes once per *compile*, not once per run — the engines
that evaluate distances inside compiled loops (the NN-chain row builds)
instead report their **measured trip counts** (``ChainResult.iters``)
and the orchestrator records ``trips × row_length`` after the run.  The
budget is therefore exact for eager pairwise calls and measured (not
estimated) for compiled loops.  Zero overhead when no scope is open:
one truthiness check on a thread-local list.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager

import jax
import jax.numpy as jnp


class DistanceBudget:
    """Tally of pairwise distance evaluations inside one accounting scope.

    ``queries`` is the total; ``by_tag`` breaks it down by call site
    (``sq_euclidean``, ``cosine``, ``rmsd``, ``row``, plus the
    orchestrator tags like ``landmark_chain``).  Budgets nest: every
    open scope on the thread sees every record, so a test can hold an
    outer budget across a code path that opens its own.
    """

    def __init__(self) -> None:
        self.queries = 0
        self.by_tag: dict[str, int] = {}

    def record(self, n_pairs: int, tag: str = "pairwise") -> None:
        n = int(n_pairs)
        if n < 0:
            raise ValueError(f"cannot record {n} distance queries")
        self.queries += n
        self.by_tag[tag] = self.by_tag.get(tag, 0) + n

    def __repr__(self) -> str:  # helpful in failed-assert output
        tags = ", ".join(f"{k}={v}" for k, v in sorted(self.by_tag.items()))
        return f"DistanceBudget(queries={self.queries}, {{{tags}}})"


_BUDGETS = threading.local()


def _budget_stack() -> list:
    stack = getattr(_BUDGETS, "stack", None)
    if stack is None:
        stack = _BUDGETS.stack = []
    return stack


@contextmanager
def count_distance_queries():
    """Open a :class:`DistanceBudget` scope on this thread.

    ::

        with count_distance_queries() as budget:
            cluster(X, "ward", algorithm="landmark")
        assert budget.queries <= 8 * (n * k + k * k)

    The landmark tests and ``benchmarks/bench_landmark.py`` use exactly
    this to *assert* the sub-quadratic claim.  Thread-local: engine
    calls dispatched to another thread (the service worker) need the
    scope opened there — :class:`~repro.service.batcher.ClusteringService`
    records its landmark-lane queries onto the submitting scope itself.
    """
    budget = DistanceBudget()
    stack = _budget_stack()
    stack.append(budget)
    try:
        yield budget
    finally:
        stack.remove(budget)


def record_queries(n_pairs: int, tag: str = "pairwise") -> None:
    """Record ``n_pairs`` distance evaluations on every open budget.

    No-op (one list-truthiness check) when no scope is open, so the hot
    paths pay nothing in production.
    """
    stack = _budget_stack()
    if not stack:
        return
    for budget in stack:
        budget.record(n_pairs, tag)


def _concrete(*arrays) -> bool:
    """True when no argument is a jax tracer — i.e. this is an eager
    host-side call that will execute exactly once, so recording it is an
    actual measurement (module docstring: traced calls are accounted by
    their orchestrator's measured trip counts instead)."""
    return not any(isinstance(a, jax.core.Tracer) for a in arrays)


def pairwise_sq_euclidean(X: jax.Array, Y: jax.Array | None = None) -> jax.Array:
    """``D[a, b] = ‖X[a] − Y[b]‖²`` via the Gram trick (MXU-friendly)."""
    self_dist = Y is None
    X = jnp.asarray(X, jnp.float32)
    Y = X if Y is None else jnp.asarray(Y, jnp.float32)
    if _concrete(X, Y):
        record_queries(X.shape[0] * Y.shape[0], "sq_euclidean")
    xx = jnp.sum(X * X, axis=-1)
    yy = jnp.sum(Y * Y, axis=-1)
    # full float32 products: a TPU's default float32 matmul rounds its
    # operands to bfloat16, and the Gram form subtracts nearly equal terms
    G = jnp.matmul(X, Y.T, precision=jax.lax.Precision.HIGHEST)
    D = xx[:, None] + yy[None, :] - 2.0 * G
    D = jnp.maximum(D, 0.0)  # clamp the tiny negatives from cancellation
    if self_dist:            # exact zeros on the diagonal
        D = D * (1.0 - jnp.eye(D.shape[0], dtype=D.dtype))
    return D


def pairwise_euclidean(X: jax.Array, Y: jax.Array | None = None) -> jax.Array:
    return jnp.sqrt(pairwise_sq_euclidean(X, Y))


def pairwise_cosine(X: jax.Array, Y: jax.Array | None = None) -> jax.Array:
    """Cosine *distance* ``1 − cos_sim`` (for embedding dedup)."""
    X = jnp.asarray(X, jnp.float32)
    Y = X if Y is None else jnp.asarray(Y, jnp.float32)
    if _concrete(X, Y):
        record_queries(X.shape[0] * Y.shape[0], "cosine")
    Xn = X / jnp.maximum(jnp.linalg.norm(X, axis=-1, keepdims=True), 1e-12)
    Yn = Y / jnp.maximum(jnp.linalg.norm(Y, axis=-1, keepdims=True), 1e-12)
    sim = jnp.matmul(Xn, Yn.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.clip(1.0 - sim, 0.0, 2.0)


def _center(P: jax.Array) -> jax.Array:
    return P - jnp.mean(P, axis=-2, keepdims=True)


#: One-sided Jacobi sweeps for a 3×3 singular value problem.  The cyclic
#: method converges quadratically: on synthetic conformations three sweeps
#: already reach the float32 floor of the RMSD formula (max error 1.5e-5
#: against a float64 SVD); the other three are margin.
_JACOBI_SWEEPS = 6


def _singular_values_3x3(H: jax.Array) -> jax.Array:
    """Singular values of a 3×3 matrix by one-sided (Hestenes) Jacobi.

    Plane rotations orthogonalize the columns of ``H``; the column norms
    are then the singular values, to high relative accuracy.  A fixed
    number of unrolled sweeps of elementwise ops: no library SVD, whose
    TPU lowering is a data-dependent loop that a ``shard_map`` body
    (the paper's distributed RMSD build) cannot type, and which batches
    far worse than this over millions of conformation pairs.
    """
    cols = [H[:, 0], H[:, 1], H[:, 2]]
    for _ in range(_JACOBI_SWEEPS):
        for p, q in ((0, 1), (0, 2), (1, 2)):
            a, b = cols[p], cols[q]
            alpha, beta, gamma = (jnp.sum(a * a), jnp.sum(b * b),
                                  jnp.sum(a * b))
            rotate = gamma != 0.0
            zeta = (beta - alpha) / jnp.where(rotate, 2.0 * gamma, 1.0)
            t = jnp.where(zeta >= 0.0, 1.0, -1.0) / (
                jnp.abs(zeta) + jnp.sqrt(1.0 + zeta * zeta)
            )
            t = jnp.where(rotate, t, 0.0)
            c = jax.lax.rsqrt(1.0 + t * t)
            s = c * t
            cols[p], cols[q] = c * a - s * b, s * a + c * b
    return jnp.stack([jnp.sqrt(jnp.sum(x * x)) for x in cols])


def kabsch_rmsd(A: jax.Array, B: jax.Array) -> jax.Array:
    """Minimum RMSD between two ``(atoms, 3)`` conformations.

    Kabsch: with centered A, B and cross-covariance ``H = Aᵀ B`` (3×3),
    the optimal-rotation RMSD satisfies
    ``rmsd² = (‖A‖² + ‖B‖² − 2·(σ₁ + σ₂ ± σ₃)) / atoms`` where σ are the
    singular values of H (σ₃ the smallest) and the sign of σ₃ is
    ``sign(det H)`` (``= sign(det(V Uᵀ))``) — reflections are not
    allowed.
    """
    A = _center(jnp.asarray(A, jnp.float32))
    B = _center(jnp.asarray(B, jnp.float32))
    atoms = A.shape[-2]
    # elementwise float32 sums (no matmul at reduced TPU precision): the
    # RMSD formula subtracts nearly equal terms
    H = jnp.sum(A[:, :, None] * B[:, None, :], axis=0)
    S = _singular_values_3x3(H)
    det = (H[0, 0] * (H[1, 1] * H[2, 2] - H[1, 2] * H[2, 1])
           - H[0, 1] * (H[1, 0] * H[2, 2] - H[1, 2] * H[2, 0])
           + H[0, 2] * (H[1, 0] * H[2, 1] - H[1, 1] * H[2, 0]))
    smallest = jnp.min(S)
    corr = jnp.sum(S) - smallest + jnp.sign(det) * smallest
    msd = (jnp.sum(A * A) + jnp.sum(B * B) - 2.0 * corr) / atoms
    return jnp.sqrt(jnp.maximum(msd, 0.0))


@jax.jit
def _pairwise_rmsd_cross(A: jax.Array, B: jax.Array) -> jax.Array:
    A = jnp.asarray(A, jnp.float32)
    B = jnp.asarray(B, jnp.float32)
    return jax.vmap(lambda a: jax.vmap(lambda b: kabsch_rmsd(a, b))(B))(A)


def pairwise_rmsd_cross(A: jax.Array, B: jax.Array) -> jax.Array:
    """``(n, atoms, 3) × (m, atoms, 3) → (n, m)`` cross RMSD.

    The rectangular counterpart of :func:`pairwise_rmsd` — used by the
    streaming-assignment path to score new conformations against the
    ``k`` cluster exemplars without re-clustering.  (Recording happens
    in this un-jitted wrapper so the budget sees every *run*, not every
    trace.)
    """
    if _concrete(A, B):
        record_queries(
            jnp.shape(A)[0] * jnp.shape(B)[0], "rmsd"
        )
    return _pairwise_rmsd_cross(A, B)


@jax.jit
def _pairwise_rmsd(confs: jax.Array) -> jax.Array:
    confs = _center(jnp.asarray(confs, jnp.float32))
    n = confs.shape[0]

    def row(a):
        return jax.vmap(lambda b: kabsch_rmsd(confs[a], confs[b]))(jnp.arange(n))

    D = jax.vmap(row)(jnp.arange(n))
    D = 0.5 * (D + D.T)  # symmetrize away SVD round-off
    return D * (1.0 - jnp.eye(n, dtype=D.dtype))


def pairwise_rmsd(confs: jax.Array) -> jax.Array:
    """``(n, atoms, 3)`` conformations → ``(n, n)`` optimal-superposition RMSD.

    This is the paper's distance-matrix build for protein structures.  The
    O(n²) 3×3 SVDs are cheap; the O(n² · atoms) cross-covariances dominate
    and vectorize onto the MXU.
    """
    if _concrete(confs):
        record_queries(jnp.shape(confs)[0] ** 2, "rmsd")
    return _pairwise_rmsd(confs)
