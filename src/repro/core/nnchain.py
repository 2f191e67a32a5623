"""Nearest-neighbor-chain merge engine — exact agglomeration in O(n²) total
work (DESIGN.md §11).

The Lance-Williams loop in :mod:`repro.core.engine` pays a full matrix
pass per merge — O(n³) work for a full run even with compaction shaving
the constant.  For the **reducible** linkage methods
(:data:`REDUCIBLE_METHODS`: single, complete, average, weighted, ward)
the classical NN-chain algorithm (Murtagh) reaches the *same dendrogram*
in O(n²) total work: grow a chain ``a → NN(a) → NN(NN(a)) → …`` of
strictly decreasing distances until two clusters are mutual nearest
neighbors, merge them, and continue from the surviving chain.
Reducibility — ``d(i,j) ≤ d(i,k), d(j,k)  ⇒  d(i∪j, k) ≥ d(i,j)`` —
guarantees the remaining chain stays a valid NN chain after the merge,
so every cluster is pushed O(1) times amortized and each push costs one
O(n) row scan.

Merges are emitted in **chain order**, not by global height; for
reducible methods a stable sort by height
(:func:`repro.core.dendrogram.canonical_order`) rewrites the list into
exactly the sequence the LW loop produces — same ``(i, j)`` slot pairs
(a cluster's slot is the minimum leaf index of its members, in both
engines) and the same heights to float tolerance (each height is the
same recurrence DAG regardless of merge order, but XLA fuses/contracts
the arithmetic differently across the two programs — last-ulp
differences, same phenomenon as the batched engines' padded-shape
nonidentity).  Equivalence is asserted
against :mod:`repro.core.engine` goldens in ``tests/test_nnchain.py``
and re-checked at benchmark scale in ``benchmarks/bench_nnchain.py``.

Two compositions share the one chain loop:

* **dense** (:func:`nn_chain`) — the ``(n, n)`` matrix in the garbage
  representation; a merge rewrites row *and* column ``i`` with two
  O(n) ``dynamic_update_slice`` passes (never a full-matrix select —
  that is the LW engine's O(n²) step this engine exists to avoid).
* **points / matrix-free** (:func:`nn_chain_from_points`) — never
  materializes the matrix.  Cluster state is an O(n·d + n) **geometric
  summary** ``(w, u, size)`` per slot; candidate distances are produced
  row-by-row as ``scale · ‖w_top − w_k‖² + u_top + u_k``, either as one
  jnp pass or tile-by-tile through the Pallas row-vs-points kernel
  (:func:`repro.kernels.pairwise.row_sq_euclidean_pallas`).  Exact for
  the methods whose LW distance is a function of that summary
  (:data:`POINTS_METHODS`, all on **squared-Euclidean** input):

  - ``ward``:    ``d(A,B) = 2·n_A n_B/(n_A+n_B) · ‖c_A − c_B‖²``
                 (Wishart form; ``w`` = centroid, ``u ≡ 0``),
  - ``average``: ``d(A,B) = ‖c_A − c_B‖² + v_A + v_B``
                 (``w`` = centroid, ``u`` = mean within-cluster scatter),
  - ``weighted``: same form over the WPGMA midpoint
                 ``w_{A∪B} = (w_A + w_B)/2``,
                 ``u_{A∪B} = (u_A + u_B)/2 + ‖w_A − w_B‖²/4``.

  ``single``/``complete`` distances are min/max pair statistics with no
  O(d) sufficient summary — they stay on the dense path (DESIGN.md §11).

Early termination (``stop_at_k`` / ``distance_threshold``) is *post-hoc*
here: the full agglomeration is O(n²) anyway, so
:func:`repro.core.api.cluster` runs it, canonicalizes, and truncates the
height-sorted prefix — the same result the LW loop's early exit returns.

**Batched compositions** (:func:`nn_chain_batched`,
:func:`nn_chain_batched_from_points`, DESIGN.md §11): the same chain
loop ``vmap``-ed over a shape bucket.  The per-lane merge target
becomes a *traced* scalar (``max(n_real − 1, 0)``) instead of the
static trip count, and the ``while_loop`` vmap batching rule then
freezes finished lanes exactly the way the LW ``distance_threshold``
loop does — a lane whose chain has emitted its last merge (or a dead
padded lane, target 0) stops contributing state updates while the
slower lanes run on.  Padded slots are born dead and masked at read,
so each lane's merge sequence is the serial engine's (heights to the
usual padded-shape float tolerance).  The batched entry points keep the
``(Db, n_real, threshold)`` operand convention of the batched LW
engines so the service AOT cache compiles them interchangeably; the
threshold operand is accepted and ignored — early stop stays post-hoc
(:func:`repro.core.dendrogram.truncate_canonical`).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.engine import LWResult, _first_where, symmetrize
from repro.core.linkage import METHODS, update_row

__all__ = [
    "REDUCIBLE_METHODS",
    "POINTS_METHODS",
    "NNCHAIN_AUTO_MIN_N",
    "NNCHAIN_BATCH_AUTO_MIN_N",
    "ChainResult",
    "nn_chain",
    "nn_chain_from_points",
    "nn_chain_from_summaries",
    "nn_chain_batched",
    "nn_chain_batched_from_points",
    "resolve_algorithm",
    "resolve_batch_algorithm",
    "resolve_matrix_free",
    "summary_distance",
    "summary_merge",
]

#: Linkage methods satisfying the reducibility inequality — the ones the
#: NN-chain algorithm is exact for.  ``centroid``/``median`` can *invert*
#: (a merge may create a nearer pair below the chain), which breaks the
#: chain invariant, so they stay on the LW loop (DESIGN.md §11).
REDUCIBLE_METHODS: tuple[str, ...] = (
    "single", "complete", "average", "weighted", "ward",
)

#: Methods the matrix-free points mode supports: their LW distance is an
#: exact function of the O(d) geometric summary on squared-Euclidean
#: input.  ``ward``'s default metric is already sqeuclidean; ``average``
#: and ``weighted`` need an explicit ``metric="sqeuclidean"``.
POINTS_METHODS: tuple[str, ...] = ("ward", "average", "weighted")

#: Smallest n for which ``algorithm="auto"`` prefers the NN-chain engine
#: over the dense LW loop (measured crossover is far lower — see
#: EXPERIMENTS.md §Perf-5 — but below this size both engines run in
#: single-digit milliseconds and auto stays on the LW path every
#: existing caller was tuned against).
NNCHAIN_AUTO_MIN_N = 256

#: Smallest *bucket* n for which batched/service ``algorithm="auto"``
#: prefers the vmapped **matrix-free** NN-chain engine over the batched
#: LW loop.  The trade differs from the serial crossover: under vmap the
#: chain loop's per-lane dynamic reads lower to gathers (~tens of ns per
#: element on XLA:CPU vs ~1 ns for the LW loop's big fused selects) and
#: ``lax.cond`` executes both branches, so the *dense* batched chain
#: only ties the compacted LW bucket (0.8–1.3x measured) and auto keeps
#: dense buckets on LW at every size.  The points composition has no
#: per-lane matrix gathers — its row build is one elementwise
#: ``(B, n, d)`` pass — and beats the compacted LW bucket ≥1.5x from
#: this bucket size up (4–11x by bucket 128–256; measured in
#: benchmarks/bench_service.py, EXPERIMENTS.md §Service).
NNCHAIN_BATCH_AUTO_MIN_N = 64

#: Smallest n for which ``matrix_free="auto"`` drops the dense matrix on
#: capable inputs: below this the (n, n) build is a few MB and the dense
#: row scan is faster than the summary arithmetic.
MATRIX_FREE_AUTO_MIN_N = 4096

_F32 = jnp.float32
_INF = jnp.float32(jnp.inf)


class ChainResult(NamedTuple):
    """:class:`~repro.core.engine.LWResult` plus the measured loop-trip
    count.

    Duck-types ``LWResult`` (``merges``/``n_merges`` first, the
    ``DistributedChainResult`` convention) and adds ``iters`` — how many
    chain-loop trips the run actually executed.  Each trip performs
    exactly ONE candidate-row build (O(n) distances dense, O(n·d) work
    points mode), so ``iters × row_length`` is the *measured* number of
    distance evaluations inside the compiled loop — the number the
    landmark tier's :class:`~repro.core.distance.DistanceBudget`
    records, since host-side hooks cannot see inside a ``while_loop``
    (DESIGN.md §15).  A clean run satisfies ``iters ≤ 2(n−1)`` pushes +
    merges; the static cap is ``4n + 8``.
    """

    merges: jax.Array
    n_merges: jax.Array
    iters: jax.Array


# ---------------------------------------------------------------------------
# knob resolution (the `cluster` API defers here)
# ---------------------------------------------------------------------------


def resolve_algorithm(
    flag: str,
    *,
    method: str,
    backend: str,
    n: int,
    variant: str = "baseline",
    compaction=None,
) -> str:
    """Canonical ``algorithm=`` switch for a ``cluster`` call.

    ``"lw"`` / ``"nnchain"`` are explicit (``"nnchain"`` validates the
    method is reducible and the backend is one the chain loop has a
    composition for: the serial single-device loop, or the sharded
    matrix-free points engine on ``backend="distributed"``
    (:func:`repro.core.distributed.distributed_nn_chain_from_points`);
    the kernel backend keeps the LW engine).  ``"auto"`` picks nnchain
    only for the *default-knob* serial path — reducible method, ``n ≥``
    :data:`NNCHAIN_AUTO_MIN_N`, baseline variant, untouched compaction —
    so callers that pin LW engine knobs (``variant=``, an explicit
    ``compaction=``) keep the engine those knobs belong to, and a
    multi-device ``auto`` backend keeps the LW row-sharded loop (the
    distributed chain is explicit opt-in).
    """
    if flag == "lw":
        return "lw"
    if flag == "nnchain":
        if method not in REDUCIBLE_METHODS:
            raise ValueError(
                f"algorithm='nnchain' needs a reducible method "
                f"{REDUCIBLE_METHODS}, got {method!r} (centroid/median can "
                "produce inversions that break the chain invariant; use "
                "algorithm='lw')"
            )
        if backend not in ("auto", "serial", "distributed"):
            raise ValueError(
                f"algorithm='nnchain' has serial and distributed "
                f"compositions; backend={backend!r} keeps the LW merge "
                "loop (pass backend='serial'/'distributed' or "
                "algorithm='lw')"
            )
        return "nnchain"
    if flag != "auto":
        raise ValueError(
            f"algorithm must be 'auto', 'lw' or 'nnchain', got {flag!r}"
        )
    if (
        method in REDUCIBLE_METHODS
        and backend == "serial"
        and n >= NNCHAIN_AUTO_MIN_N
        and variant == "baseline"
        and compaction in (None, "auto")
    ):
        return "nnchain"
    return "lw"


def resolve_batch_algorithm(
    flag: str,
    *,
    method: str,
    engine: str,
    bucket_n: int,
    variant: str = "baseline",
    compaction="auto",
    points_capable: bool = False,
) -> str:
    """Canonical ``algorithm=`` switch for one batched/service bucket.

    Mirrors :func:`resolve_algorithm` with the batched trade-offs:
    ``"nnchain"`` is explicit (reducible method, ``serial`` vmap engine —
    the distributed/kernel batch engines keep the LW loop; the dense
    composition is exact but only ties the compacted LW bucket on CPU),
    and ``"auto"`` routes a bucket to the vmapped chain only where it
    *measures* faster: a **matrix-free** bucket (``points_capable`` —
    ``(n, d)`` points input under a :data:`POINTS_METHODS`
    squared-Euclidean convention) of :data:`NNCHAIN_BATCH_AUTO_MIN_N` or
    larger, on the default-knob serial path (baseline variant, untouched
    compaction).  Dense buckets stay on LW under ``auto``: the chain
    loop's per-lane gathers eat its O(n) asymptotic edge at every bucket
    size the grid serves (constant documented at
    :data:`NNCHAIN_BATCH_AUTO_MIN_N`).  Resolved per *bucket*, not per
    batch: one ragged ``cluster_batch`` may legitimately run small
    buckets on LW and large points buckets on nnchain.
    """
    if flag == "lw":
        return "lw"
    if flag == "nnchain":
        if method not in REDUCIBLE_METHODS:
            raise ValueError(
                f"algorithm='nnchain' needs a reducible method "
                f"{REDUCIBLE_METHODS}, got {method!r} (centroid/median can "
                "produce inversions that break the chain invariant; use "
                "algorithm='lw')"
            )
        if engine not in ("auto", "serial"):
            raise ValueError(
                f"batched algorithm='nnchain' is the vmapped single-device "
                f"chain; engine={engine!r} keeps the LW merge loop (pass "
                "engine='serial' or algorithm='lw')"
            )
        return "nnchain"
    if flag != "auto":
        raise ValueError(
            f"algorithm must be 'auto', 'lw' or 'nnchain', got {flag!r}"
        )
    if (
        points_capable
        and method in POINTS_METHODS
        and engine == "serial"
        and bucket_n >= NNCHAIN_BATCH_AUTO_MIN_N
        and variant == "baseline"
        and compaction in (None, False, "auto")
    ):
        return "nnchain"
    return "lw"


def resolve_matrix_free(
    flag,
    *,
    points_shape: tuple | None,
    method: str,
    metric: str | None,
    n: int,
) -> bool:
    """Canonical ``matrix_free=`` switch for the nnchain path.

    ``True`` demands the matrix-free points mode (raises when the input
    or method cannot support it); ``False`` pins the dense matrix;
    ``"auto"`` goes matrix-free exactly when it is *exact and worth it* —
    ``(n, d)`` points input, a :data:`POINTS_METHODS` method under its
    squared-Euclidean convention, and ``n ≥``
    :data:`MATRIX_FREE_AUTO_MIN_N` (where the dense matrix starts to
    cost real memory).
    """
    capable = (
        points_shape is not None
        and len(points_shape) == 2
        and method in POINTS_METHODS
        and metric == "sqeuclidean"
    )
    if flag in (False, None):
        return False
    if flag is True:
        if not capable:
            raise ValueError(
                "matrix_free=True needs (n, d) points input and a method "
                f"whose LW distance is a geometric-summary function "
                f"({POINTS_METHODS}, squared-Euclidean metric); got "
                f"method={method!r}, metric={metric!r}, "
                f"input shape {points_shape}"
            )
        return True
    if flag != "auto":
        raise ValueError(
            f"matrix_free must be a bool or 'auto', got {flag!r}"
        )
    return capable and n >= MATRIX_FREE_AUTO_MIN_N


# ---------------------------------------------------------------------------
# the ONE chain loop
# ---------------------------------------------------------------------------


class NNState(NamedTuple):
    """Carry of the chain loop — shared by both compositions.

    ``rep`` is the cluster representation: ``(D,)`` for the dense
    composition, ``(W, u)`` geometric summaries for points mode.
    ``chain``/``chain_len`` is the NN chain as a fixed-size stack
    (entries past ``chain_len`` are stale garbage).  ``iters`` counts
    loop trips — a static ``4n`` cap bounds the loop against float
    pathologies (NaN rows would otherwise cycle forever); a clean run
    never reaches it (pushes are bounded by ``2(n−1)``).
    """

    rep: tuple
    alive: jax.Array
    sizes: jax.Array
    chain: jax.Array
    chain_len: jax.Array
    merges: jax.Array
    n_merges: jax.Array
    iters: jax.Array


class NNChainOps(NamedTuple):
    """The two primitives a chain-loop composition supplies.

    row:   ``(state, top) -> (n,)`` current *raw* distances from cluster
           ``top`` to every slot — ONE O(n) (dense) / O(n·d) (points)
           pass.  The chain loop owns the liveness mask (dead slots and
           ``top`` itself go ``+inf`` before the min), so the raw row
           can be handed to ``merge`` unmasked.
    merge: ``(state, i, j, dmin, top, row_top) -> state`` — commit the
           merge into the representation (O(n) dense row rewrite, O(d)
           summary update), leaving ``alive``/``sizes`` untouched (the
           shared skeleton owns that bookkeeping).  ``row_top`` is the
           raw ``row(state, top)`` already computed this trip — the
           dense composition reuses it as the ``top`` side of the LW
           recurrence instead of paying a second per-lane row read
           (under vmap those reads are per-lane gathers, the dominant
           batched cost).
    """

    row: Callable[[NNState, jax.Array], jax.Array]
    merge: Callable[..., NNState]


def _scalar_set(vec: jax.Array, idx: jax.Array, value) -> jax.Array:
    """O(1) element write as a dynamic-update-slice (never a scatter —
    the XLA:CPU scatter path costs ~µs per element)."""
    upd = jnp.asarray(value, vec.dtype)[None]
    return jax.lax.dynamic_update_slice(vec, upd, (idx,))


def _chain_loop(
    ops: NNChainOps, state: NNState, n_steps: int | jax.Array
) -> NNState:
    """Run the NN-chain loop until ``n_steps`` merges are recorded.

    Each trip either *extends* the chain by the tip's nearest neighbor
    or *merges* the top two elements when they are mutual nearest
    neighbors.  Mutuality is detected by preferring the previous chain
    element on distance ties (``row[prev] == m`` picks ``prev``): the
    chain's distances are non-increasing, so an equality at the tip IS
    reciprocity — and the preference also rules out tie cycles revisiting
    older chain entries.  All index bookkeeping is dynamic-update-slice,
    never a scatter, and the argmin is the engine's vectorized
    min + first-index recovery (XLA:CPU scalarizes variadic reduces).

    ``n_steps`` may be a *traced* scalar (the batched compositions pass
    each lane's ``max(n_real − 1, 0)``): the merge buffer's static row
    count comes from :func:`_init_state`, and under ``vmap`` the
    while_loop batching rule turns the per-lane cond into
    ``any(cond)`` + per-lane ``select`` — lanes whose target is met stop
    absorbing body results while slower lanes run on (the frozen-lane
    invariant, same mechanism as the LW ``distance_threshold`` loop).
    """
    if isinstance(n_steps, int) and n_steps <= 0:
        return state
    n = state.alive.shape[0]
    ks = jnp.arange(n)
    iter_cap = jnp.int32(4 * n + 8)

    def cond(s: NNState):
        return (s.n_merges < n_steps) & (s.iters < iter_cap)

    def body(s: NNState) -> NNState:
        empty = s.chain_len == 0
        first_live = _first_where(s.alive, ks, n).astype(jnp.int32)
        chain = _scalar_set(
            s.chain, jnp.int32(0), jnp.where(empty, first_live, s.chain[0])
        )
        length = jnp.where(empty, jnp.int32(1), s.chain_len)
        top = jax.lax.dynamic_index_in_dim(chain, length - 1, keepdims=False)
        prev = jnp.where(
            length >= 2,
            jax.lax.dynamic_index_in_dim(
                chain, jnp.maximum(length - 2, 0), keepdims=False
            ),
            jnp.int32(n),
        )
        row_raw = ops.row(s, top)
        row = jnp.where(s.alive & (ks != top), row_raw, _INF)
        m = jnp.min(row)
        prev_hit = (prev < n) & (row[jnp.minimum(prev, n - 1)] == m)
        c = jnp.where(
            prev_hit, prev, _first_where(row == m, ks, n).astype(jnp.int32)
        )

        def do_merge(s: NNState) -> NNState:
            i, j = jnp.minimum(top, c), jnp.maximum(top, c)
            new_size = s.sizes[i] + s.sizes[j]
            s = ops.merge(s, i, j, m, top, row_raw)
            record = jnp.stack(
                [i.astype(_F32), j.astype(_F32), m, new_size]
            )[None, :]
            return s._replace(
                alive=_scalar_set(s.alive, j, False),
                sizes=_scalar_set(
                    _scalar_set(s.sizes, i, new_size), j, 0.0
                ),
                merges=jax.lax.dynamic_update_slice(
                    s.merges, record, (s.n_merges, jnp.int32(0))
                ),
                n_merges=s.n_merges + 1,
                chain=chain,
                chain_len=length - 2,
            )

        def do_push(s: NNState) -> NNState:
            return s._replace(
                chain=_scalar_set(chain, length, c),
                chain_len=length + 1,
            )

        s = jax.lax.cond(prev_hit, do_merge, do_push, s)
        return s._replace(iters=s.iters + 1)

    return jax.lax.while_loop(cond, body, state)


def _init_state(
    rep: tuple, alive: jax.Array, n_steps: int, sizes: jax.Array | None = None
) -> NNState:
    """Fresh chain-loop carry.  ``sizes`` defaults to unit weight per live
    slot (leaves); the summaries entry point passes pre-accumulated
    cluster sizes (two-phase tier, slots are whole clusters)."""
    n = alive.shape[0]
    return NNState(
        rep=rep,
        alive=alive,
        sizes=alive.astype(_F32) if sizes is None else sizes,
        chain=jnp.zeros((n,), jnp.int32),
        chain_len=jnp.zeros((), jnp.int32),
        merges=jnp.zeros((max(n_steps, 0), 4), _F32),
        n_merges=jnp.zeros((), jnp.int32),
        iters=jnp.zeros((), jnp.int32),
    )


# ---------------------------------------------------------------------------
# dense composition
# ---------------------------------------------------------------------------


def _dense_nnchain_ops(method: str, n: int) -> NNChainOps:
    """Garbage-representation dense primitives: mask at read, and — the
    load-bearing trick — **row-only writes with a version vector**.

    A merge must update slot ``i``'s distances for every future reader.
    The obvious commit (row *and* column ``i``) is O(n) cells, but a
    *column* ``dynamic_update_slice`` on the loop-carried matrix defeats
    XLA:CPU's in-place buffer reuse and silently copies all O(n²) cells
    per merge — measured, it turns the whole engine cubic (EXPERIMENTS.md
    §Perf-5).  So the merge writes ONLY row ``i`` (a genuine in-place
    DUS) and bumps ``version[i]`` to the merge index; any later read of
    slot ``t``'s distances reconstructs the current row from whichever
    side was written more recently::

        d(t, k) = D[k, t]  if version[k] > version[t]   (column read)
                  D[t, k]  otherwise                    (row read)

    — correct because a slot's cluster only changes when its row is
    rewritten, so the later write of the pair saw the other side's
    current state.  Both reads are O(n) slices; dead slots hold inert
    garbage masked at read.
    """
    ks = jnp.arange(n)

    def current_row(rep: tuple, t: jax.Array) -> jax.Array:
        D, ver = rep
        r_row = jax.lax.dynamic_slice_in_dim(D, t, 1, axis=0)[0]
        r_col = jax.lax.dynamic_slice(D, (jnp.int32(0), t), (n, 1))[:, 0]
        return jnp.where(ver > ver[t], r_col, r_row)

    def row(s: NNState, top: jax.Array) -> jax.Array:
        return current_row(s.rep, top)

    def merge(s: NNState, i, j, dmin, top, row_top) -> NNState:
        D, ver = s.rep
        # {i, j} == {top, c}: top's current row was computed this trip,
        # so only the partner pays a fresh (gathering) row read
        row_c = current_row(s.rep, jnp.where(top == i, j, i))
        d_ki = jnp.where(top == i, row_top, row_c)
        d_kj = jnp.where(top == i, row_c, row_top)
        keep = s.alive & (ks != i) & (ks != j)
        new = update_row(method, d_ki, d_kj, dmin, s.sizes[i], s.sizes[j],
                         s.sizes)
        new = jnp.where(keep, new, 0.0)        # garbage rep: dead cells inert
        D = jax.lax.dynamic_update_slice(D, new[None, :], (i, jnp.int32(0)))
        ver = _scalar_set(ver, i, s.n_merges + 1)
        return s._replace(rep=(D, ver))

    return NNChainOps(row=row, merge=merge)


@partial(jax.jit, static_argnames=("method",))
def _run_dense(D: jax.Array, *, method: str) -> ChainResult:
    D = symmetrize(D)
    n = D.shape[0]
    rep = (D, jnp.zeros((n,), jnp.int32))
    state = _init_state(rep, jnp.ones((n,), bool), n - 1)
    out = _chain_loop(_dense_nnchain_ops(method, n), state, n - 1)
    return ChainResult(merges=out.merges, n_merges=out.n_merges,
                       iters=out.iters)


def nn_chain(D: jax.Array, method: str = "complete") -> ChainResult:
    """Full agglomeration of an ``(n, n)`` distance matrix via NN-chain.

    O(n²) total work, exact for the reducible methods.  Merges are in
    **chain order** — pass them through
    :func:`repro.core.dendrogram.canonical_order` before cutting (the
    ``cluster`` API does this for you); the canonicalized list matches
    the LW engine's output on tie-free input.
    """
    if method not in METHODS:
        raise ValueError(f"unknown linkage method {method!r}")
    if method not in REDUCIBLE_METHODS:
        raise ValueError(
            f"nn_chain is exact only for reducible methods "
            f"{REDUCIBLE_METHODS}, got {method!r}"
        )
    D = jnp.asarray(D, _F32)
    if D.ndim != 2 or D.shape[0] != D.shape[1]:
        raise ValueError(f"distance matrix must be square, got {D.shape}")
    if D.shape[0] < 2:
        return ChainResult(merges=jnp.zeros((0, 4), _F32),
                           n_merges=jnp.zeros((), jnp.int32),
                           iters=jnp.zeros((), jnp.int32))
    return _run_dense(D, method=method)


# ---------------------------------------------------------------------------
# matrix-free points composition
# ---------------------------------------------------------------------------


def summary_distance(method, sq, u_k, u_top, n_k, n_top):
    """LW distance from geometric summaries, given ``sq = ‖w_top − w_k‖²``.

    Broadcasts: ``sq``/``u_k``/``n_k`` may be any shape (a full candidate
    row, or one shard's local slice of it — the distributed composition
    passes the slice), ``u_top``/``n_top`` are the tip's scalars.  Shared
    by the serial, batched and sharded chain engines so their distances
    stay bit-identical (the cross-engine equivalence tests rely on it).
    """
    if method == "ward":
        return 2.0 * n_top * n_k / (n_top + n_k) * sq
    return sq + u_k + u_top                     # average / weighted


def summary_merge(method, w_i, w_j, u_i, u_j, n_i, n_j):
    """Merge two geometric summaries — the O(d) recursion per method.

    Returns ``(w_new, u_new)`` for the union cluster.  ``ward`` keeps the
    size-weighted centroid (Wishart form, ``u ≡ 0``); ``average`` adds
    the exact mean within-cluster scatter combination; ``weighted`` is
    the WPGMA midpoint recursion.  One definition serves the serial,
    batched, sharded and two-phase compositions.
    """
    tot = n_i + n_j
    gap = jnp.sum((w_i - w_j) ** 2)
    if method == "weighted":                # WPGMA midpoint recursion
        w_new = 0.5 * (w_i + w_j)
        u_new = 0.5 * (u_i + u_j) + 0.25 * gap
    elif method == "average":               # size-weighted centroid + scatter
        w_new = (n_i * w_i + n_j * w_j) / tot
        u_new = (n_i * u_i + n_j * u_j) / tot + (n_i * n_j) / (tot * tot) * gap
    else:                                   # ward: centroid only, u ≡ 0
        w_new = (n_i * w_i + n_j * w_j) / tot
        u_new = jnp.zeros((), _F32)
    return w_new, u_new


def _points_nnchain_ops(
    method: str, n: int, *, use_pallas: bool, block_n: int, interpret: bool
) -> NNChainOps:
    """Geometric-summary primitives — O(n·d) row build, O(d) merge.

    The squared-norm row ``‖w_top − w_k‖²`` is the only O(n·d) term; it
    runs through the shared row-build dispatch
    (:func:`repro.kernels.pairwise.row_sq_euclidean`) — one jnp pass by
    default, or tile-by-tile through the Pallas row-vs-points kernel
    when ``use_pallas`` (compiled on a TPU, interpreted elsewhere).
    Everything else is O(n) epilogue.
    """
    del n  # summaries broadcast; kept for signature stability

    def row(s: NNState, top: jax.Array) -> jax.Array:
        from repro.kernels.pairwise import row_sq_euclidean

        W, u = s.rep
        w_top = jax.lax.dynamic_slice_in_dim(W, top, 1, axis=0)[0]
        sq = row_sq_euclidean(w_top, W, use_pallas=use_pallas,
                              block_n=block_n, interpret=interpret)
        return summary_distance(method, sq, u, u[top], s.sizes, s.sizes[top])

    def merge(s: NNState, i, j, dmin, top, row_top) -> NNState:
        W, u = s.rep
        w_i = jax.lax.dynamic_slice_in_dim(W, i, 1, axis=0)[0]
        w_j = jax.lax.dynamic_slice_in_dim(W, j, 1, axis=0)[0]
        w_new, u_new = summary_merge(
            method, w_i, w_j, u[i], u[j], s.sizes[i], s.sizes[j]
        )
        W = jax.lax.dynamic_update_slice(W, w_new[None, :], (i, jnp.int32(0)))
        return s._replace(rep=(W, _scalar_set(u, i, u_new)))

    return NNChainOps(row=row, merge=merge)


@partial(jax.jit, static_argnames=("method", "n_steps", "use_pallas",
                                   "block_n", "interpret"))
def _run_points(
    X: jax.Array,
    alive: jax.Array,
    *,
    method: str,
    n_steps: int,
    use_pallas: bool,
    block_n: int,
    interpret: bool,
) -> ChainResult:
    n = X.shape[0]
    rep = (jnp.asarray(X, _F32), jnp.zeros((n,), _F32))
    state = _init_state(rep, alive, n_steps)
    ops = _points_nnchain_ops(
        method, n, use_pallas=use_pallas, block_n=block_n, interpret=interpret
    )
    out = _chain_loop(ops, state, n_steps)
    return ChainResult(merges=out.merges, n_merges=out.n_merges,
                       iters=out.iters)


def nn_chain_from_points(
    X: jax.Array,
    method: str = "ward",
    *,
    use_pallas: bool = False,
    block_n: int = 512,
    interpret: bool | None = None,
) -> ChainResult:
    """Matrix-free full agglomeration of ``(n, d)`` points — O(n·d + n)
    peak memory, the ``(n, n)`` matrix is **never** allocated.

    Exact (to float tolerance) against the dense engines run on
    ``pairwise_sq_euclidean(X)`` for :data:`POINTS_METHODS` — the
    squared-Euclidean convention is ``ward``'s default and must be
    requested explicitly (``metric="sqeuclidean"``) for
    ``average``/``weighted`` at the ``cluster`` level.  Merges are in
    chain order, same contract as :func:`nn_chain`.

    ``use_pallas`` routes the per-tip squared-norm row through the tiled
    Pallas row-vs-points kernel (pads ``n`` to a ``block_n`` multiple
    and ``d`` to a lane multiple once, up front; padded slots are born
    dead).  The absence of any (n, n) intermediate is asserted over the
    compiled HLO in ``benchmarks/bench_nnchain.py``.
    """
    if method not in POINTS_METHODS:
        raise ValueError(
            f"matrix-free points mode supports {POINTS_METHODS} (their LW "
            f"distance is a geometric-summary function), got {method!r} — "
            "build the distance matrix and use nn_chain instead"
        )
    X = jnp.asarray(X, _F32)
    if X.ndim != 2:
        raise ValueError(f"expected (n, d) points, got {X.shape}")
    n = int(X.shape[0])
    if n < 2:
        return ChainResult(merges=jnp.zeros((0, 4), _F32),
                           n_merges=jnp.zeros((), jnp.int32),
                           iters=jnp.zeros((), jnp.int32))
    if use_pallas:
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        # block stays a 128-lane multiple — Mosaic rejects off-tile blocks
        bn = max(128, min(block_n, n) // 128 * 128)
        n_pad = n + (-n) % bn
        d_pad = X.shape[1] + (-X.shape[1]) % 128
        X = jnp.pad(X, ((0, n_pad - n), (0, d_pad - X.shape[1])))
        alive = jnp.arange(n_pad) < n
        return _run_points(X, alive, method=method, n_steps=n - 1,
                           use_pallas=True, block_n=bn, interpret=interpret)
    return _run_points(X, jnp.ones((n,), bool), method=method, n_steps=n - 1,
                       use_pallas=False, block_n=block_n, interpret=False)


@partial(jax.jit, static_argnames=("method", "n_steps"))
def _run_summaries(
    W: jax.Array,
    u: jax.Array,
    sizes: jax.Array,
    *,
    method: str,
    n_steps: int,
) -> ChainResult:
    n = W.shape[0]
    state = _init_state(
        (W, u), jnp.ones((n,), bool), n_steps, sizes=sizes
    )
    ops = _points_nnchain_ops(
        method, n, use_pallas=False, block_n=512, interpret=False
    )
    out = _chain_loop(ops, state, n_steps)
    return ChainResult(merges=out.merges, n_merges=out.n_merges,
                       iters=out.iters)


def nn_chain_from_summaries(
    W: jax.Array,
    u: jax.Array,
    sizes: jax.Array,
    method: str = "ward",
) -> ChainResult:
    """Agglomerate ``k`` pre-accumulated geometric summaries.

    Each slot is a whole *cluster* — ``W[k]`` its summary point
    (centroid / WPGMA midpoint), ``u[k]`` its scatter term, ``sizes[k]``
    its member count — and the chain runs the same
    :func:`summary_distance`/:func:`summary_merge` recursions as
    :func:`nn_chain_from_points` (which is exactly this call with unit
    sizes and ``u = 0``).  This is phase 2 of the two-phase distributed
    tier (:func:`repro.core.distributed.two_phase_from_points`): shards
    cluster locally, then their surviving summaries agglomerate globally
    here.  Merges are in chain order over summary slots; recorded sizes
    are summed member counts.
    """
    if method not in POINTS_METHODS:
        raise ValueError(
            f"summary agglomeration supports {POINTS_METHODS} (their LW "
            f"distance is a geometric-summary function), got {method!r}"
        )
    W = jnp.asarray(W, _F32)
    if W.ndim != 2:
        raise ValueError(f"expected (k, d) summary points, got {W.shape}")
    k = int(W.shape[0])
    u = jnp.asarray(u, _F32)
    sizes = jnp.asarray(sizes, _F32)
    if u.shape != (k,) or sizes.shape != (k,):
        raise ValueError(
            f"u and sizes must be ({k},) to match the summaries, got "
            f"{u.shape} and {sizes.shape}"
        )
    if k < 2:
        return ChainResult(merges=jnp.zeros((0, 4), _F32),
                           n_merges=jnp.zeros((), jnp.int32),
                           iters=jnp.zeros((), jnp.int32))
    return _run_summaries(W, u, sizes, method=method, n_steps=k - 1)


# ---------------------------------------------------------------------------
# batched compositions (vmap over a shape bucket)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("method", "n_steps"))
def _run_batch(
    Db: jax.Array,
    n_real: jax.Array,
    threshold: jax.Array,
    *,
    method: str,
    n_steps: int,
) -> LWResult:
    """Vmapped dense NN-chain over a ``(B, n, n)`` bucket.

    Same ``(Db, n_real, threshold)`` operand convention as
    :func:`repro.core.batched._run_vmap` so the service AOT cache lowers
    both through one code path.  ``threshold`` is accepted and ignored:
    the chain emits merges in chain order, so early stop is post-hoc
    canonical truncation (module docstring) — the operand only keeps the
    compiled signature uniform.  ``n_steps`` is the *static* merge-buffer
    capacity (``bucket_n − 1``); each lane's actual target is the traced
    ``max(n_real − 1, 0)``, and dead padded lanes (target 0) never
    absorb a body result.
    """
    del threshold  # post-hoc early stop; operand kept for AOT uniformity
    Db = symmetrize(Db)
    n = Db.shape[-1]

    def run(D: jax.Array, n_r: jax.Array) -> LWResult:
        alive = jnp.arange(n) < n_r
        rep = (jnp.where(alive[:, None] & alive[None, :], D, 0.0),
               jnp.zeros((n,), jnp.int32))
        state = _init_state(rep, alive, n_steps)
        target = jnp.minimum(jnp.maximum(n_r - 1, 0), n_steps).astype(jnp.int32)
        out = _chain_loop(_dense_nnchain_ops(method, n), state, target)
        return LWResult(merges=out.merges, n_merges=out.n_merges)

    return jax.vmap(run)(Db, jnp.asarray(n_real, jnp.int32))


def nn_chain_batched(
    Db: jax.Array, n_real, method: str = "complete"
) -> LWResult:
    """Batched NN-chain over a ``(B, n, n)`` shape bucket.

    Lane ``b`` agglomerates ``Db[b, :n_real[b], :n_real[b]]``; rows and
    columns past ``n_real[b]`` are padding (born dead, masked at read).
    Returns stacked chain-order merge buffers — lane ``b``'s real rows
    are ``merges[b, :n_real[b] - 1]``; pass them through
    :func:`repro.core.dendrogram.canonical_order` before cutting, same
    contract as :func:`nn_chain` (``cluster_batch`` does this for you).
    """
    if method not in METHODS:
        raise ValueError(f"unknown linkage method {method!r}")
    if method not in REDUCIBLE_METHODS:
        raise ValueError(
            f"nn_chain is exact only for reducible methods "
            f"{REDUCIBLE_METHODS}, got {method!r}"
        )
    Db = jnp.asarray(Db, _F32)
    if Db.ndim != 3 or Db.shape[1] != Db.shape[2]:
        raise ValueError(
            f"expected a (B, n, n) bucket of distance matrices, got {Db.shape}"
        )
    n_real = jnp.asarray(n_real, jnp.int32)
    if n_real.shape != (Db.shape[0],):
        raise ValueError(
            f"n_real must be ({Db.shape[0]},) to match the bucket, "
            f"got {n_real.shape}"
        )
    n = int(Db.shape[1])
    if n < 2:
        return LWResult(
            merges=jnp.zeros((Db.shape[0], 0, 4), _F32),
            n_merges=jnp.zeros((Db.shape[0],), jnp.int32),
        )
    return _run_batch(Db, n_real, jnp.float32(jnp.inf),
                      method=method, n_steps=n - 1)


@partial(jax.jit, static_argnames=("method", "n_steps"))
def _run_points_batch(
    Xb: jax.Array,
    n_real: jax.Array,
    threshold: jax.Array,
    *,
    method: str,
    n_steps: int,
) -> LWResult:
    """Vmapped matrix-free NN-chain over a ``(B, n, d)`` points bucket —
    pad waste is O(n·d) per lane instead of the dense bucket's O(n²),
    and the per-trip row build has no per-lane matrix gathers at all
    (only ``(B, d)`` summary reads) — the measured service win
    (EXPERIMENTS.md §Service).  ``threshold`` is accepted and ignored,
    same post-hoc contract as :func:`_run_batch`."""
    del threshold  # post-hoc early stop; operand kept for AOT uniformity
    n = Xb.shape[1]

    def run(X: jax.Array, n_r: jax.Array) -> LWResult:
        alive = jnp.arange(n) < n_r
        rep = (jnp.asarray(X, _F32), jnp.zeros((n,), _F32))
        state = _init_state(rep, alive, n_steps)
        target = jnp.minimum(jnp.maximum(n_r - 1, 0), n_steps).astype(jnp.int32)
        ops = _points_nnchain_ops(
            method, n, use_pallas=False, block_n=512, interpret=False
        )
        out = _chain_loop(ops, state, target)
        return LWResult(merges=out.merges, n_merges=out.n_merges)

    return jax.vmap(run)(Xb, jnp.asarray(n_real, jnp.int32))


def nn_chain_batched_from_points(
    Xb: jax.Array, n_real, method: str = "ward"
) -> LWResult:
    """Batched matrix-free agglomeration of a ``(B, n, d)`` points bucket.

    Lane ``b`` clusters ``Xb[b, :n_real[b]]`` under the squared-Euclidean
    convention of :func:`nn_chain_from_points` (:data:`POINTS_METHODS`
    only); padding rows are inert.  The ``(n, n)`` matrix is never
    materialized in any lane, so a ragged bucket wastes O(n·d) per
    padded lane, not O(n²).  Merges are in chain order, same contract as
    :func:`nn_chain_batched`.
    """
    if method not in POINTS_METHODS:
        raise ValueError(
            f"matrix-free points mode supports {POINTS_METHODS} (their LW "
            f"distance is a geometric-summary function), got {method!r} — "
            "build the distance matrices and use nn_chain_batched instead"
        )
    Xb = jnp.asarray(Xb, _F32)
    if Xb.ndim != 3:
        raise ValueError(f"expected a (B, n, d) points bucket, got {Xb.shape}")
    n_real = jnp.asarray(n_real, jnp.int32)
    if n_real.shape != (Xb.shape[0],):
        raise ValueError(
            f"n_real must be ({Xb.shape[0]},) to match the bucket, "
            f"got {n_real.shape}"
        )
    n = int(Xb.shape[1])
    if n < 2:
        return LWResult(
            merges=jnp.zeros((Xb.shape[0], 0, 4), _F32),
            n_merges=jnp.zeros((Xb.shape[0],), jnp.int32),
        )
    return _run_points_batch(Xb, n_real, jnp.float32(jnp.inf),
                             method=method, n_steps=n - 1)
