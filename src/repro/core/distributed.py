"""Distributed Lance-Williams clustering — the paper's contribution, on a mesh.

Faithful mapping of the paper's §5.3 algorithm (see DESIGN.md §4 for the
step-by-step correspondence).  The ``(n, n)`` distance matrix is
**block-row sharded** across every device of a 1-D logical mesh axis
``'p'`` (the paper's processor ring); per merge iteration:

  paper step 1   → each shard computes its local masked min        O(n²/p)
  paper step 2-3 → one ``all_gather`` of the p ``(lmin, i, j)`` triples
  paper step 4-5 → every shard *replicates* the global argmin (the paper's
                   observation that no further communication is needed)
  paper step 6a  → rows ``i`` and ``j`` are broadcast with a single
                   owner-contributes ``psum``  (O(2n) bytes — the collective
                   form of the paper's row/col owner sends)
  paper step 6b  → every shard applies the LW recurrence to its slice of
                   column ``i``; the owner rewrites row ``i``; row/col ``j``
                   is tombstoned via the replicated ``alive`` mask

The loop body is :func:`repro.core.engine.make_sharded_body` — the
unified merge loop composed with the collective argmin/fetch/write
primitives — run inside one ``shard_map``-ped program (no host
round-trips).  Storage per device is ``n²/p`` elements — the paper's
headline scaling — verified in ``benchmarks/bench_storage.py``.

``variant='rowmin'``/``'lazy'`` select the cached-row-minima argmin ops
(fastcluster-style, beyond paper; EXPERIMENTS.md §Perf), and
``stop_at_k``/``distance_threshold`` early-terminate the loop — both are
engine-level knobs shared with every other backend.

Two more engines live here, taking the paper's storage thesis *past* the
n²/p it claimed (DESIGN.md §12):

* :func:`distributed_nn_chain_from_points` — the sharded **matrix-free
  NN-chain**: the ``(n, d)`` points are block-row sharded, the O(n)
  geometric-summary bookkeeping is replicated, and the chain loop runs
  inside one ``shard_map``-ped program where each trip builds only the
  *local slice* of the chain-tip candidate row and elects the global
  nearest neighbor with ONE ``all_gather`` of per-shard ``(min, argmin,
  prev)`` triples (plus two O(d) owner-contributes ``psum`` summary
  broadcasts).  Per-device storage is O(n·d/p + n) — no (n, n), no
  (n/p, n) buffer anywhere in the compiled HLO — and the merges are the
  serial chain's exactly (same float ops per distance, same
  tie-breaking).  A segmented driver turns :mod:`repro.distributed.fault`
  failure injection into bounded same-segment retries (the sharded state
  *is* the checkpoint).
* :func:`two_phase_from_points` — the explicitly **approximate**
  two-phase tier (Variance-based Distributed Clustering,
  arXiv 1703.09823): each shard clusters its block locally with the
  serial chain, truncates at ``intermediate_k`` clusters, and the
  surviving geometric summaries agglomerate globally.  Zero per-step
  collectives; quality is measured (merge-set agreement vs the exact
  engine) in ``benchmarks/bench_distributed.py``, not assumed.
"""

from __future__ import annotations

import math
import time
import warnings
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.engine import (
    AXIS,
    VARIANTS,
    LWResult,
    _first_where,
    make_sharded_body,
    match_vma,
    resolve_compaction,
    resolve_n_steps,
    symmetrize,
)
from repro.core.linkage import METHODS
from repro.core.nnchain import (
    POINTS_METHODS,
    NNState,
    _scalar_set,
    nn_chain_from_points,
    nn_chain_from_summaries,
    summary_distance,
    summary_merge,
)
from repro.distributed.fault import SimulatedFailure, StepDeadline
from repro.obs import NULL_TRACER, Tracer, get_registry


class DistributedChainResult(NamedTuple):
    """:class:`~repro.core.engine.LWResult` plus run telemetry.

    Duck-types ``LWResult`` (``merges``/``n_merges`` first, so every
    existing consumer keeps working) and carries what the segmented
    driver previously only logged: how many segments it dispatched, how
    many shard-loss restarts it absorbed, how many segments straggled
    past the deadline.  The same counts feed the process-global metrics
    registry (``distributed_chain_*`` counters, DESIGN.md §13).
    ``iters`` is the chain-loop trip count, as on
    :class:`~repro.core.nnchain.ChainResult`.
    """

    merges: jax.Array
    n_merges: jax.Array
    restarts: int = 0
    stragglers: int = 0
    segments: int = 0
    iters: jax.Array | None = None      # chain-loop trips, all segments


def make_cluster_mesh(devices=None) -> Mesh:
    """1-D mesh over all (or the given) devices — the paper's processor set."""
    devices = list(jax.devices() if devices is None else devices)
    return Mesh(np.array(devices), (AXIS,))


def flatten_mesh(mesh: Mesh) -> Mesh:
    """View any N-D production mesh as the paper's 1-D processor ring."""
    return Mesh(mesh.devices.reshape(-1), (AXIS,))


def require_ring_mesh(mesh: Mesh | None) -> Mesh:
    """Validate the mesh every clustering engine runs on — ONE gate shared
    by the dense row-sharded loop and the matrix-free chain.

    ``None`` builds the default 1-D mesh over all devices.  A multi-axis
    production mesh is rejected with instructions rather than silently
    reshaped: the engines' collectives name a single axis, and guessing a
    flattening order behind the caller's back reorders shard ownership.
    """
    if mesh is None:
        return make_cluster_mesh()
    if len(mesh.axis_names) != 1:
        raise ValueError(
            f"the distributed clustering engines run on a 1-D mesh (the "
            f"paper's processor ring), got a {len(mesh.axis_names)}-axis "
            f"mesh with axes {tuple(mesh.axis_names)} of shape "
            f"{tuple(mesh.devices.shape)} — choose the device order "
            "explicitly with repro.core.distributed.flatten_mesh(mesh) "
            "or build one with make_cluster_mesh(devices)"
        )
    return mesh


def pad_to_mesh(n: int, p: int, *, block: int = 1) -> int:
    """Smallest padded size ≥ ``n`` divisible by ``p · block`` — the ONE
    divisibility rule shared by the dense and matrix-free paths.

    Every shard must own the same number of rows (``shard_map`` is
    SPMD), and a Pallas-tiled row build additionally needs each shard's
    rows to be a multiple of its ``block``.  Padding slots are born dead
    and masked at read everywhere.
    """
    if p < 1:
        raise ValueError(f"mesh must have at least one device, got p={p}")
    if block < 1:
        raise ValueError(f"block must be positive, got {block}")
    q = p * block
    return max(math.ceil(n / q), 1) * q


def _pad_matrix(D: np.ndarray | jax.Array, n_pad: int) -> jax.Array:
    D = jnp.asarray(D, jnp.float32)
    n = D.shape[0]
    if n_pad == n:
        return D
    out = jnp.zeros((n_pad, n_pad), jnp.float32)
    return out.at[:n, :n].set(D)


@partial(
    jax.jit,
    static_argnames=("method", "n_steps", "mesh", "variant", "with_threshold",
                     "compaction"),
)
def _run(
    D,
    alive0,
    sizes0,
    threshold=0.0,
    *,
    method: str,
    n_steps: int,
    mesh: Mesh,
    variant: str,
    with_threshold: bool = False,
    compaction: bool = False,
):
    # the threshold is a traced replicated operand (only None-vs-set is
    # structural), so distinct dedup radii share one compiled program
    body = make_sharded_body(
        method, n_steps, variant, with_threshold=with_threshold,
        compaction=compaction,
    )
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(AXIS, None), P(), P(), P()),
        out_specs=(P(), P()),
    )(D, alive0, sizes0, jnp.asarray(threshold, jnp.float32))


def distributed_lance_williams(
    D,
    method: str = "complete",
    mesh: Mesh | None = None,
    variant: str = "baseline",
    *,
    stop_at_k: int = 1,
    distance_threshold: float | None = None,
    compaction: bool | str = "auto",
) -> LWResult:
    """Cluster an ``(n, n)`` distance matrix across every device of *mesh*.

    The matrix is padded to a multiple of the device count (padding slots are
    born dead) and block-row sharded; the result merge list is replicated.
    ``compaction`` enables the engine's stage schedule (DESIGN.md §3): at
    each power-of-two boundary the live rows are re-sharded into
    ``size/2p``-row blocks, so per-device storage shrinks as the run
    progresses; ``"auto"`` turns it on whenever the plan has more than
    one stage.
    """
    if method not in METHODS:
        raise ValueError(f"unknown linkage method {method!r}")
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; pick from {VARIANTS}")
    mesh = require_ring_mesh(mesh)
    p = mesh.devices.size

    n = int(D.shape[0])
    n_pad = pad_to_mesh(n, p)
    Dp = symmetrize(_pad_matrix(D, n_pad))      # single input-normalization path

    alive0 = (jnp.arange(n_pad) < n)
    sizes0 = alive0.astype(jnp.float32)

    n_steps = resolve_n_steps(n, stop_at_k)
    Dp = jax.device_put(Dp, NamedSharding(mesh, P(AXIS, None)))
    merges, n_merges = _run(
        Dp,
        alive0,
        sizes0,
        jnp.float32(0.0 if distance_threshold is None else distance_threshold),
        method=method,
        n_steps=n_steps,
        mesh=mesh,
        variant=variant,
        with_threshold=distance_threshold is not None,
        compaction=resolve_compaction(compaction, n_pad, n_steps, align=p),
    )
    return LWResult(merges=merges, n_merges=n_merges)


# ---------------------------------------------------------------------------
# distributed distance-matrix build (the paper's parallel RMSD phase)
# ---------------------------------------------------------------------------


def distributed_pairwise(
    X, kind: str = "sqeuclidean", mesh: Mesh | None = None
) -> jax.Array:
    """Build the sharded ``(n, n)`` distance matrix row-block by row-block.

    Each shard holds an ``(n/p, d)`` slice of the points, all-gathers the
    full point set once, and emits its row block — the matrix is *born
    sharded* exactly as the clustering engine consumes it (the paper's
    "as the data files were read in from disk they were sent to the
    processors").
    """
    mesh = require_ring_mesh(mesh)
    p = mesh.devices.size
    X = jnp.asarray(X, jnp.float32)
    n = X.shape[0]
    n_pad = pad_to_mesh(n, p)
    if n_pad != n:
        X = jnp.concatenate([X, jnp.zeros((n_pad - n,) + X.shape[1:], X.dtype)], 0)

    Xs = jax.device_put(X, NamedSharding(mesh, P(AXIS, *([None] * (X.ndim - 1)))))
    D = pairwise_program(kind, mesh, X.ndim)(Xs)
    return D[:n, :n] if n_pad != n else D


def pairwise_program(kind: str, mesh: Mesh, ndim: int):
    """The jitted row-sharded build behind :func:`distributed_pairwise`
    for ``ndim``-dimensional input (2: points, 3: conformations)."""
    from repro.core import distance as dist

    def body(X_local):
        X_full = jax.lax.all_gather(X_local, AXIS, tiled=True)
        if kind == "sqeuclidean":
            return dist.pairwise_sq_euclidean(X_local, X_full)
        if kind == "euclidean":
            return dist.pairwise_euclidean(X_local, X_full)
        if kind == "cosine":
            return dist.pairwise_cosine(X_local, X_full)
        if kind == "rmsd":
            rows = jax.vmap(
                lambda a: jax.vmap(lambda b: dist.kabsch_rmsd(a, b))(X_full)
            )(X_local)
            return rows
        raise ValueError(f"unknown distance kind {kind!r}")

    return jax.jit(
        shard_map(
            body,
            mesh=mesh,
            in_specs=(P(AXIS, *([None] * (ndim - 1))),),
            out_specs=P(AXIS, None),
        )
    )


# ---------------------------------------------------------------------------
# sharded matrix-free NN-chain (DESIGN.md §12) — O(n·d/p + n) per device
# ---------------------------------------------------------------------------

_F32 = jnp.float32
_INF = jnp.float32(jnp.inf)


def _make_sharded_chain_body(
    method: str, *, use_pallas: bool, block_n: int, interpret: bool
):
    """One chain trip per while-loop iteration, SPMD across the ring.

    Data layout: the summary points ``W`` are block-row sharded (each
    shard owns rows ``[s·n/p, (s+1)·n/p)``); every other piece of state —
    scatter terms ``u``, ``alive``, ``sizes``, the chain stack, the merge
    list — is O(n) and replicated.  Per trip, exactly three collectives:

      1. ``psum``  — owner-contributes broadcast of the chain tip's
                     summary point ``w_top``           (O(d) bytes)
      2. ``all_gather`` — per-shard ``(local min, local argmin, prev's
                     masked value)`` triples; every shard replicates the
                     global election                    (O(3p) bytes)
      3. ``psum``  — owner-contributes broadcast of the elected
                     candidate's summary ``w_c``       (O(d) bytes)

    The candidate row itself is never assembled: each shard computes only
    its ``‖w_top − w_local‖²`` slice (through the shared
    :func:`repro.kernels.pairwise.row_sq_euclidean` dispatch — one jnp
    pass or Pallas tiles) and reduces it to one scalar before the
    collective.  Election ties resolve to the first shard attaining the
    min, then its first local index — exactly the serial loop's
    first-index tie-breaking, so the merge sequence is the serial chain's
    (distances are the same float ops on the same values).  The ``w_c``
    broadcast is hoisted OUT of the merge-vs-push branch so no collective
    sits inside ``lax.cond``.
    """

    def body(W_local, u0, alive0, sizes0, chain0, chain_len0,
             merges0, n_merges0, iters0, target):
        from repro.kernels.pairwise import row_sq_euclidean

        rows, _ = W_local.shape
        n_pad = alive0.shape[0]
        p = n_pad // rows
        offset = jax.lax.axis_index(AXIS).astype(jnp.int32) * rows
        local_ids = offset + jnp.arange(rows, dtype=jnp.int32)
        ks = jnp.arange(n_pad)
        shard_ids = jnp.arange(p)
        iter_cap = jnp.int32(4 * n_pad + 8)

        def owner_bcast(W_loc, slot):
            """Summary point of *slot*, contributed by its owner — O(d)."""
            own = (slot >= offset) & (slot < offset + rows)
            lr = jnp.clip(slot - offset, 0, rows - 1)
            w = jax.lax.dynamic_slice_in_dim(W_loc, lr, 1, axis=0)[0]
            return jax.lax.psum(jnp.where(own, w, 0.0), AXIS)

        def cond(s: NNState):
            return (s.n_merges < target) & (s.iters < iter_cap)

        def trip(s: NNState) -> NNState:
            W_loc, u = s.rep
            empty = s.chain_len == 0
            first_live = _first_where(s.alive, ks, n_pad).astype(jnp.int32)
            chain = _scalar_set(
                s.chain, jnp.int32(0),
                jnp.where(empty, first_live, s.chain[0]),
            )
            length = jnp.where(empty, jnp.int32(1), s.chain_len)
            top = jax.lax.dynamic_index_in_dim(
                chain, length - 1, keepdims=False
            )
            prev = jnp.where(
                length >= 2,
                jax.lax.dynamic_index_in_dim(
                    chain, jnp.maximum(length - 2, 0), keepdims=False
                ),
                jnp.int32(n_pad),
            )
            # collective 1: tip summary to everyone
            w_top = owner_bcast(W_loc, top)
            u_top = jax.lax.dynamic_index_in_dim(u, top, keepdims=False)
            n_top = jax.lax.dynamic_index_in_dim(s.sizes, top, keepdims=False)
            # local slice of the candidate row — the only O(n·d/p) term
            sq = row_sq_euclidean(w_top, W_loc, use_pallas=use_pallas,
                                  block_n=block_n, interpret=interpret)
            u_loc = jax.lax.dynamic_slice_in_dim(u, offset, rows)
            sizes_loc = jax.lax.dynamic_slice_in_dim(s.sizes, offset, rows)
            alive_loc = jax.lax.dynamic_slice_in_dim(s.alive, offset, rows)
            dloc = summary_distance(method, sq, u_loc, u_top,
                                    sizes_loc, n_top)
            masked = jnp.where(alive_loc & (local_ids != top), dloc, _INF)
            lmin = jnp.min(masked)
            larg = offset + _first_where(
                masked == lmin, jnp.arange(rows), rows
            ).astype(jnp.int32)
            own_prev = (prev >= offset) & (prev < offset + rows)
            lp = jnp.clip(prev - offset, 0, rows - 1)
            pval = jnp.where(
                own_prev,
                jax.lax.dynamic_index_in_dim(masked, lp, keepdims=False),
                _INF,
            )
            # collective 2: elect the global (min, argmin) + prev's value
            trip_vec = jnp.stack([lmin, larg.astype(_F32), pval])
            allt = jax.lax.all_gather(trip_vec, AXIS)          # (p, 3)
            m = jnp.min(allt[:, 0])
            win = _first_where(allt[:, 0] == m, shard_ids, p)
            c0 = jax.lax.dynamic_index_in_dim(
                allt[:, 1], win, keepdims=False
            ).astype(jnp.int32)
            prev_hit = (prev < n_pad) & (jnp.min(allt[:, 2]) == m)
            c = jnp.where(prev_hit, prev, c0)
            # collective 3: candidate summary — hoisted out of the cond
            w_c = owner_bcast(W_loc, c)

            def do_merge(s: NNState) -> NNState:
                W_loc, u = s.rep
                i, j = jnp.minimum(top, c), jnp.maximum(top, c)
                w_i = jnp.where(top < c, w_top, w_c)
                w_j = jnp.where(top < c, w_c, w_top)
                u_i = jax.lax.dynamic_index_in_dim(u, i, keepdims=False)
                u_j = jax.lax.dynamic_index_in_dim(u, j, keepdims=False)
                n_i = jax.lax.dynamic_index_in_dim(
                    s.sizes, i, keepdims=False
                )
                n_j = jax.lax.dynamic_index_in_dim(
                    s.sizes, j, keepdims=False
                )
                w_new, u_new = summary_merge(
                    method, w_i, w_j, u_i, u_j, n_i, n_j
                )
                new_size = n_i + n_j
                # O(d) owner-local commit: non-owners rewrite a row with
                # its own current value (a genuine in-place DUS either way)
                own_i = (i >= offset) & (i < offset + rows)
                li = jnp.clip(i - offset, 0, rows - 1)
                cur = jax.lax.dynamic_slice_in_dim(W_loc, li, 1, axis=0)
                upd = jnp.where(own_i, w_new[None, :], cur)
                W_loc = jax.lax.dynamic_update_slice(
                    W_loc, upd, (li, jnp.int32(0))
                )
                record = jnp.stack(
                    [i.astype(_F32), j.astype(_F32), m, new_size]
                )[None, :]
                return s._replace(
                    rep=(W_loc, _scalar_set(u, i, u_new)),
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
            return match_vma(s._replace(iters=s.iters + 1))

        # the carry mixes the shard-varying W block and replicated O(n)
        # state; the loop types everything varying, the epilogue reduces
        state = match_vma(NNState(
            rep=(W_local, u0), alive=alive0, sizes=sizes0, chain=chain0,
            chain_len=chain_len0, merges=merges0, n_merges=n_merges0,
            iters=iters0,
        ))
        out = jax.lax.while_loop(cond, trip, state)
        # replicated outputs are bitwise equal across shards by
        # construction (collective results are); the pmax epilogue
        # re-establishes *tracked* replication for out_specs=P()
        rmax = lambda x: jax.lax.pmax(x, AXIS)  # noqa: E731
        return (
            out.rep[0],
            rmax(out.rep[1]),
            rmax(out.alive.astype(jnp.int32)).astype(bool),
            rmax(out.sizes),
            rmax(out.chain),
            rmax(out.chain_len),
            rmax(out.merges),
            rmax(out.n_merges),
            rmax(out.iters),
        )

    return body


@partial(
    jax.jit,
    static_argnames=("method", "mesh", "use_pallas", "block_n", "interpret"),
)
def _run_sharded_chain(
    W, u, alive, sizes, chain, chain_len, merges, n_merges, iters, target,
    *, method: str, mesh: Mesh, use_pallas: bool, block_n: int,
    interpret: bool,
):
    # `target` is a traced replicated operand: every segment of a
    # segmented run (and every restart) reuses ONE compiled program
    body = _make_sharded_chain_body(
        method, use_pallas=use_pallas, block_n=block_n, interpret=interpret
    )
    rep = P()
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(AXIS, None), rep, rep, rep, rep, rep, rep, rep, rep,
                  rep),
        out_specs=(P(AXIS, None), rep, rep, rep, rep, rep, rep, rep, rep),
    )(W, u, alive, sizes, chain, chain_len, merges, n_merges, iters,
      jnp.asarray(target, jnp.int32))


def _fault_event(log, msg: str) -> None:
    if log is not None:
        log(msg)
    else:
        warnings.warn(msg, RuntimeWarning, stacklevel=3)


#: Logical→mesh axis mapping for the sharded chain state: only the
#: points/summary rows are sharded (over the paper's ring axis); every
#: bookkeeping leaf is replicated.
_CHAIN_ROW_RULES = {"rows": (AXIS,)}


def _chain_state_specs(n_pad: int, d_pad: int, n: int):
    """ParamSpec mirror of the sharded chain state tuple, in state order."""
    from repro.models.common import ParamSpec

    rep = ParamSpec((n_pad,), (None,))
    scalar = ParamSpec((), ())
    return (
        ParamSpec((n_pad, d_pad), ("rows", None)),     # W
        rep,                                           # u
        rep,                                           # alive
        rep,                                           # sizes
        rep,                                           # chain
        scalar,                                        # chain_len
        ParamSpec((n - 1, 4), (None, None)),           # merges
        scalar,                                        # n_merges
        scalar,                                        # iters
    )


def _shrink_chain_state(state, fallback_mesh: Mesh, *, n_pad: int,
                        d_pad: int, n: int, exhausted_p: int, cause, log):
    """Validate + reshard the live chain state onto the fallback mesh.

    Validation runs BEFORE any state moves
    (:func:`repro.checkpoint.elastic.validate_mesh_for_tree`), so an
    incompatible fallback fails with the offending leaves and axes named
    and the last consistent state still intact on the original mesh.
    """
    from repro.checkpoint.elastic import reshard_tree, validate_mesh_for_tree
    from repro.distributed.sharding import tree_shardings

    mesh2 = require_ring_mesh(fallback_mesh)
    p2 = int(mesh2.devices.size)
    specs = _chain_state_specs(n_pad, d_pad, n)
    problems = validate_mesh_for_tree(specs, _CHAIN_ROW_RULES, mesh2)
    if problems:
        raise RuntimeError(
            f"restart budget exhausted on the p={exhausted_p} mesh, and the "
            f"fallback mesh (p={p2}) cannot hold the sharded chain state:"
            "\n  " + "\n  ".join(problems) + "\n"
            "the last consistent state is still on the original mesh — "
            "pick a fallback whose size divides the padded row count"
        ) from cause
    _fault_event(
        log,
        f"[fault] restart budget exhausted on p={exhausted_p} — resharding "
        f"the chain state onto the p={p2} fallback mesh and continuing "
        "(same segment, fresh budget; no merges lost)",
    )
    return mesh2, reshard_tree(
        state, tree_shardings(specs, _CHAIN_ROW_RULES, mesh2)
    )


def distributed_nn_chain_from_points(
    X,
    method: str = "ward",
    mesh: Mesh | None = None,
    *,
    use_pallas: bool = False,
    block_n: int = 512,
    interpret: bool | None = None,
    segment_steps: int | None = None,
    failure_plan=None,
    max_restarts: int = 2,
    fallback_mesh: Mesh | None = None,
    deadline: StepDeadline | None = None,
    log=None,
    tracer: Tracer | None = None,
) -> DistributedChainResult:
    """Sharded matrix-free agglomeration of ``(n, d)`` points — the exact
    serial NN-chain, run across every device of *mesh* with
    **O(n·d/p + n)** per-device storage (DESIGN.md §12).

    The points are padded (:func:`pad_to_mesh`) and block-row sharded
    (:func:`repro.distributed.sharding.shard_rows`); the O(n)
    bookkeeping is replicated; the whole chain loop runs inside one
    ``shard_map``-ped ``while_loop`` with three small collectives per
    trip (see :func:`_make_sharded_chain_body`).  Merges come back in
    chain order, identical to :func:`repro.core.nnchain.nn_chain_from_points`
    on the same input — the per-shard row slices are the same float ops
    the serial row pass runs, and election ties break to the globally
    first index.  Canonicalize with
    :func:`repro.core.dendrogram.canonical_order` before cutting
    (``cluster(algorithm="nnchain", backend="distributed")`` does).

    ``use_pallas`` routes each shard's row slice through the tiled
    Pallas kernel (pads every shard's rows to a ``block_n`` multiple and
    ``d`` to a lane multiple, once).

    **Fault tolerance** (:mod:`repro.distributed.fault`): with
    ``segment_steps`` the run dispatches the same compiled program in
    bounded segments; ``failure_plan.check(segment)`` injects a shard
    loss *between* collectives, and recovery is a same-segment retry —
    the on-device sharded state is the checkpoint, no merges are lost —
    bounded by ``max_restarts`` (then a diagnosable ``RuntimeError``).
    A :class:`~repro.distributed.fault.StepDeadline` flags straggling
    segments (delayed shard) through ``log``/``RuntimeWarning``.

    **Elastic shrink** (:mod:`repro.checkpoint.elastic`): with a
    ``fallback_mesh``, exhausting the restart budget does not kill the
    run — the sharded state is validated against the fallback
    (:func:`~repro.checkpoint.elastic.validate_mesh_for_tree`; an
    incompatible mesh raises a ``RuntimeError`` naming the offending
    leaves and axes *before* any state moves), resharded onto it
    (:func:`~repro.checkpoint.elastic.reshard_tree`), and the same
    segment retried there with a fresh restart budget.  One shrink per
    run — a mesh that keeps failing has a problem restarts can't fix.

    **Telemetry** (DESIGN.md §13): the returned
    :class:`DistributedChainResult` carries ``restarts`` /
    ``stragglers`` / ``segments``; the same counts land on the
    process-global registry (``distributed_chain_segments_total``,
    ``..._restarts_total``, ``..._straggler_segments_total``) and, with
    a ``tracer``, every segment dispatch becomes a ``chain_segment``
    span in the exported trace.  All of it host-side — the compiled
    program is untouched.
    """
    if method not in POINTS_METHODS:
        raise ValueError(
            f"the sharded matrix-free chain supports {POINTS_METHODS} "
            f"(their LW distance is a geometric-summary function), got "
            f"{method!r} — use the dense distributed LW engine instead"
        )
    X = jnp.asarray(X, _F32)
    if X.ndim != 2:
        raise ValueError(f"expected (n, d) points, got {X.shape}")
    n, d = int(X.shape[0]), int(X.shape[1])
    if n < 2:
        return DistributedChainResult(
            merges=jnp.zeros((0, 4), _F32),
            n_merges=jnp.zeros((), jnp.int32),
            iters=jnp.zeros((), jnp.int32),
        )
    mesh = require_ring_mesh(mesh)
    p = int(mesh.devices.size)

    if use_pallas:
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        # every shard's rows must tile: block is a 128-lane multiple
        bn = max(128, min(block_n, pad_to_mesh(n, p) // p) // 128 * 128)
        n_pad = pad_to_mesh(n, p, block=bn)
        d_pad = d + (-d) % 128
    else:
        interpret = False
        bn = block_n
        n_pad = pad_to_mesh(n, p)
        d_pad = d
    if (n_pad, d_pad) != (n, d):
        X = jnp.pad(X, ((0, n_pad - n), (0, d_pad - d)))

    from repro.distributed.sharding import replicate, shard_rows

    alive = jnp.arange(n_pad) < n
    state = (
        shard_rows(X, mesh),                                   # W  (n·d/p)
        replicate(jnp.zeros((n_pad,), _F32), mesh),            # u
        replicate(alive, mesh),                                # alive
        replicate(alive.astype(_F32), mesh),                   # sizes
        replicate(jnp.zeros((n_pad,), jnp.int32), mesh),       # chain
        replicate(jnp.zeros((), jnp.int32), mesh),             # chain_len
        replicate(jnp.zeros((n - 1, 4), _F32), mesh),          # merges
        replicate(jnp.zeros((), jnp.int32), mesh),             # n_merges
        replicate(jnp.zeros((), jnp.int32), mesh),             # iters
    )

    n_steps = n - 1
    seg = n_steps if segment_steps is None else max(1, int(segment_steps))
    tracer = tracer or NULL_TRACER
    reg = get_registry()
    seg_counter = reg.counter(
        "distributed_chain_segments_total", "Segment dispatches")
    restart_counter = reg.counter(
        "distributed_chain_restarts_total", "Shard-loss same-segment retries")
    straggler_counter = reg.counter(
        "distributed_chain_straggler_segments_total",
        "Segments past the straggler deadline")
    shrink_counter = reg.counter(
        "distributed_chain_shrinks_total",
        "Elastic reshard-to-fallback-mesh events")
    done, seg_idx, restarts, stragglers = 0, 0, 0, 0
    while done < n_steps:
        target = min(done + seg, n_steps)
        t0 = time.perf_counter()
        try:
            if failure_plan is not None:
                failure_plan.check(seg_idx)
            state = _run_sharded_chain(
                *state, target, method=method, mesh=mesh,
                use_pallas=use_pallas, block_n=bn, interpret=interpret,
            )
            made = int(state[7])        # syncs the segment (timing + fault)
        except SimulatedFailure as e:
            restarts += 1
            restart_counter.inc()
            tracer.add_span(
                "chain_segment", t0, time.perf_counter(), cat="distributed",
                segment=seg_idx, error="shard-lost", restarts=restarts,
            )
            if restarts > max_restarts:
                if fallback_mesh is None:
                    raise RuntimeError(
                        f"distributed NN-chain lost a shard at segment "
                        f"{seg_idx} and exceeded max_restarts={max_restarts} "
                        f"(committed {done}/{n_steps} merges, p={p}, n={n}); "
                        "the last consistent sharded state is still on the "
                        "mesh — re-dispatch with a fresh failure budget to "
                        "continue, or pass fallback_mesh= to shrink "
                        "elastically"
                    ) from e
                # elastic shrink: validate (loudly, naming offending
                # leaves/axes) then reshard the live state; same segment
                # retried on the smaller mesh with a fresh budget
                mesh, state = _shrink_chain_state(
                    state, fallback_mesh, n_pad=n_pad, d_pad=d_pad, n=n,
                    exhausted_p=p, cause=e, log=log,
                )
                p = int(mesh.devices.size)
                fallback_mesh = None    # one shrink per run
                restarts = 0
                shrink_counter.inc()
                continue
            _fault_event(
                log,
                f"[fault] {e} — retrying segment {seg_idx} "
                f"({restarts}/{max_restarts}); the sharded state is the "
                "checkpoint, no merges lost",
            )
            continue
        t1 = time.perf_counter()
        dt = t1 - t0
        seg_counter.inc()
        tracer.add_span(
            "chain_segment", t0, t1, cat="distributed",
            segment=seg_idx, merges_done=int(state[7]), target=target,
        )
        if deadline is not None and deadline.observe(dt):
            stragglers += 1
            straggler_counter.inc()
            _fault_event(
                log,
                f"[fault] segment {seg_idx} straggled ({dt:.3f}s > "
                f"{deadline.factor}x median) — delayed shard flagged; "
                "run continues",
            )
        seg_idx += 1
        if made < target:               # iteration cap inside the segment
            done = made
            break
        done = made
    if done != n_steps:
        raise RuntimeError(
            "sharded NN-chain hit its iteration cap before finishing — "
            "the input likely contains NaNs (the chain invariant needs a "
            f"total order on distances); committed {done}/{n_steps} merges"
        )
    return DistributedChainResult(
        merges=state[6], n_merges=state[7],
        restarts=restarts, stragglers=stragglers, segments=seg_idx,
        iters=state[8],
    )


# ---------------------------------------------------------------------------
# two-phase approximate tier (Variance-based Distributed Clustering)
# ---------------------------------------------------------------------------


def _replay_summaries(X: np.ndarray, merges: np.ndarray, method: str):
    """Replay a merge prefix through the geometric-summary recursions.

    Host-side float32 mirror of :func:`repro.core.nnchain.summary_merge`:
    walking the phase-1 merge prefix rebuilds exactly the ``(w, u, size)``
    state each surviving cluster would carry — including WPGMA's
    tree-dependent midpoints, which cannot be computed from members
    alone.  Returns ``(W, u, sizes, alive)`` over the shard's slots.
    """
    m = X.shape[0]
    W = np.array(X, np.float32, copy=True)
    u = np.zeros(m, np.float32)
    sizes = np.ones(m, np.float32)
    alive = np.ones(m, bool)
    for row in np.asarray(merges):
        i, j = int(round(row[0])), int(round(row[1]))
        n_i, n_j = sizes[i], sizes[j]
        tot = n_i + n_j
        gap = np.float32(((W[i] - W[j]) ** 2).sum())
        if method == "weighted":
            w_new = np.float32(0.5) * (W[i] + W[j])
            u_new = np.float32(0.5) * (u[i] + u[j]) + np.float32(0.25) * gap
        elif method == "average":
            w_new = (n_i * W[i] + n_j * W[j]) / tot
            u_new = (n_i * u[i] + n_j * u[j]) / tot \
                + (n_i * n_j) / (tot * tot) * gap
        else:                                   # ward
            w_new = (n_i * W[i] + n_j * W[j]) / tot
            u_new = np.float32(0.0)
        W[i], u[i], sizes[i], alive[j] = w_new, u_new, tot, False
    return W, u, sizes, alive


def two_phase_from_points(
    X,
    method: str = "ward",
    *,
    shards: int | None = None,
    intermediate_k: int | None = None,
) -> LWResult:
    """Approximate two-phase agglomeration (arXiv 1703.09823's scheme):
    cluster each shard's block locally, agglomerate summaries globally.

    Phase 1 runs the serial matrix-free chain on each of ``shards``
    contiguous blocks and truncates its canonical merge list at
    ``intermediate_k`` clusters (default ``⌈√(block size)⌉``); phase 2
    replays those prefixes into geometric summaries
    (:func:`_replay_summaries`) and agglomerates the surviving
    ``Σ intermediate_k`` summaries with
    :func:`repro.core.nnchain.nn_chain_from_summaries`.  The stitched
    result is a full ``(n−1, 4)`` merge list in global slot convention —
    structurally valid, heights monotone-repaired
    (phase-2 heights may genuinely dip below another shard's phase-1
    heights; the repair lifts them, which is part of the approximation) —
    but NOT the exact dendrogram: no merge may cross shards below the
    truncation level.  The quality delta is *measured* as merge-set
    agreement (:func:`repro.core.dendrogram.merge_set_agreement`) in
    ``benchmarks/bench_distributed.py`` / EXPERIMENTS.md; the exact
    engines are one ``algorithm=`` flag away.
    """
    from repro.core import dendrogram as dg

    if method not in POINTS_METHODS:
        raise ValueError(
            f"the two-phase tier supports {POINTS_METHODS} (phase 2 "
            f"agglomerates geometric summaries), got {method!r}"
        )
    X = np.asarray(X, np.float32)
    if X.ndim != 2:
        raise ValueError(f"expected (n, d) points, got {X.shape}")
    n = X.shape[0]
    if n < 2:
        return LWResult(merges=np.zeros((0, 4), np.float32),
                        n_merges=np.int32(0))
    p = int(shards) if shards is not None else max(1, jax.device_count())
    if p < 1:
        raise ValueError(f"shards must be positive, got {shards}")
    p = min(p, n)
    base = math.ceil(n / p)

    stitched: list = []
    reps: list[int] = []
    Wg, ug, szg = [], [], []
    for o in range(0, n, base):
        Xs = X[o:o + base]
        m = Xs.shape[0]
        k_s = (intermediate_k if intermediate_k is not None
               else max(1, int(round(math.sqrt(m)))))
        k_s = max(1, min(int(k_s), m))
        if m >= 2 and m - k_s > 0:
            res = nn_chain_from_points(jnp.asarray(Xs), method)
            if int(res.n_merges) != m - 1:
                raise RuntimeError(
                    f"phase-1 chain on shard at offset {o} hit its "
                    "iteration cap (NaNs in the input?)"
                )
            local = dg.canonical_order(np.asarray(res.merges), n=m)[: m - k_s]
        else:
            local = np.zeros((0, 4), np.float32)
        W, u, sizes, alive = _replay_summaries(Xs, local, method)
        for row in local:
            stitched.append((o + row[0], o + row[1], row[2], row[3]))
        for s in np.flatnonzero(alive):
            reps.append(o + int(s))
            Wg.append(W[s]); ug.append(u[s]); szg.append(sizes[s])

    K = len(reps)
    if K >= 2:
        res2 = nn_chain_from_summaries(
            np.stack(Wg), np.array(ug, np.float32),
            np.array(szg, np.float32), method,
        )
        if int(res2.n_merges) != K - 1:
            raise RuntimeError(
                "phase-2 summary chain hit its iteration cap "
                "(NaNs in the input?)"
            )
        m2 = np.asarray(res2.merges)
        reps_arr = np.asarray(reps, np.float32)
        # summaries are enumerated in ascending global-slot order, so the
        # i<j slot convention survives the index mapping unchanged
        mapped = m2.copy()
        mapped[:, 0] = reps_arr[m2[:, 0].astype(np.int64)]
        mapped[:, 1] = reps_arr[m2[:, 1].astype(np.int64)]
        stitched.extend(map(tuple, mapped))

    merges = np.asarray(stitched, np.float32).reshape(-1, 4)
    # monotone repair (unbounded clamp budget) + canonical height sort:
    # emission order is dependency order, so the repaired stable sort is
    # structurally valid by construction — canonical_order re-validates
    merges = dg.canonical_order(merges, n=n, rtol=1e30)
    return LWResult(merges=merges, n_merges=np.int32(merges.shape[0]))
