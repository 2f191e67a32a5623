"""Unified Lance-Williams merge-loop engine (DESIGN.md §3–§4, §9).

The paper's algorithm is ONE loop — find the global minimum, apply the
Lance-Williams recurrence, tombstone the absorbed slot, record the tree
level.  This module is the single implementation of that loop; every
public backend (serial / kernelized / distributed / batched) is a thin
composition of it.  A step is assembled from pluggable primitives:

* **argmin op** — how step 1 finds the next merge candidate:
  dense hierarchical row-min (``baseline``), cached row-minima
  (``rowmin``), cached row-minima with a bounded dirty-row drain
  (``lazy``), the Pallas min-scan kernel, or per-shard local min +
  ``all_gather`` (the paper's distributed step 1–5, all three variants).
* **update op** — how step 6 rewrites the merged row: the fused jnp
  ``update_row`` or the Pallas ``lw_update`` kernel.
* **execution wrapper** — plain ``fori_loop``/``while_loop`` on one
  device, ``vmap`` over problems, ``shard_map`` over matrix rows (the
  paper's processor ring), or ``shard_map`` over whole problems.

Two storage representations, both from DESIGN.md §3's dense+tombstone
idiom, are selected by the primitives:

* **premasked** (dense jnp paths): the liveness/diagonal mask is applied
  once up front and maintained in place — tombstoned rows/columns are
  overwritten with ``+inf`` as they die, so step 1 is a plain vector
  min with no per-step mask rebuild.
* **garbage** (kernel and row-sharded paths): dead cells hold inert
  garbage and the ``alive`` mask is applied at argmin time (the Pallas
  min-scan masks in VMEM; the sharded argmin masks its row block).

Both representations feed the recurrence identical live values, so merge
lists are bit-identical across jnp backends and index-identical for the
kernels (float-tolerance distances) — asserted in ``tests/test_engine.py``.

Early termination is an engine-level feature every backend inherits:
``stop_at_k`` statically shrinks the trip count to ``n - k`` merges, and
``distance_threshold`` switches the trip loop to a ``while_loop`` that
exits before the first merge whose distance exceeds the threshold.
(How these knobs compose with ``variant``/``compaction``/``algorithm``
across entry points is specified once, in
:func:`repro.core.api.cluster`'s docstring.)

This loop does O(n²) work **per merge** (O(n³) per run; compaction
shaves the constant).  For the reducible linkage methods the NN-chain
engine (:mod:`repro.core.nnchain`, DESIGN.md §11) reaches the identical
dendrogram in O(n²) *total* — ``cluster(algorithm="auto")`` picks it
for large serial problems; this loop remains the engine for
centroid/median, for the distributed/kernel/batched execution wrappers,
and for every ``variant``/``compaction`` configuration.

**Compaction schedule** (DESIGN.md §3).  The static-shape loop touches
the full dense matrix every trip, so after ``n/2`` merges half of every
pass is tombstone traffic.  :func:`plan_stages` splits the run into
power-of-two stages: once the live count has provably halved (after
``size - size//2`` merges — every trip tombstones one slot, and
exhausted/ragged lanes are already below the bound), one gather pass
packs the live rows/columns into the next-smaller ``(size/2, size/2)``
matrix plus a slot→original-id remap table, and the loop continues at
the smaller shape.  Live slots keep their relative order, so row-major
first-minimum tie-breaking — and therefore the merge sequence — is
unchanged; emitted merges are remapped back to original slot ids and
stay index-identical (bit-identical on the jnp paths).  Total dense work
drops from ~n³ to ~0.57·n³ touched cells.  All stages trace into ONE
compiled program, so an AOT-cached executable covers the whole schedule.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.linkage import update_row

#: Mesh axis name of the paper's 1-D processor ring (shared by every
#: sharded wrapper; ``core.distributed`` re-exports it).
AXIS = "p"

#: Argmin-op variants available on every backend.
VARIANTS: tuple[str, ...] = ("baseline", "rowmin", "lazy")

#: Bounded per-drain-trip rescan width of the ``lazy`` variant.
LAZY_BATCH_K = 8

#: Smallest matrix a compaction stage may shrink to.  Below this the
#: per-stage gather/sort overhead outweighs the saved tombstone traffic
#: (EXPERIMENTS.md §Perf iteration 4); the plan keeps the tail of the
#: run at this size instead of halving further.
MIN_STAGE_N = 32

_F32 = jnp.float32
_INF = jnp.float32(jnp.inf)


def match_vma(tree):
    """Make every leaf vary over the union of the leaves' manual axes.

    Inside ``shard_map`` a loop carry must keep its type across a trip:
    a leaf that starts replicated (a zero merge list, a fresh cache) but
    comes back shard-varying (written from a shard's block) fails the
    ``while_loop``/``fori_loop`` carry check.  Loops apply this to the
    initial carry and to each trip's result, so both sides agree.
    Outside ``shard_map`` every leaf's ``vma`` is empty and this is the
    identity.
    """
    leaves = jax.tree.leaves(tree)
    axes = frozenset().union(*(jax.typeof(x).vma for x in leaves))
    if not axes:
        return tree

    def cast(x):
        missing = tuple(sorted(axes - jax.typeof(x).vma))
        return jax.lax.pcast(x, missing, to="varying") if missing else x

    return jax.tree.map(cast, tree)


# ---------------------------------------------------------------------------
# compaction schedule (static stage plan + the live-slot gather pass)
# ---------------------------------------------------------------------------


def plan_stages(
    n: int,
    n_steps: int,
    *,
    min_stage: int = MIN_STAGE_N,
    align: int = 1,
) -> tuple[tuple[int, int], ...]:
    """Static compaction schedule: ``((size, steps), ...)``.

    Stage 0 runs at full size ``n``; each later stage runs on the
    ``size//2`` matrix produced by one gather pass.  A stage boundary is
    only legal once the live count provably fits the half-size matrix —
    after ``size - size//2`` merges, since every trip tombstones one
    slot and lanes that ran out of live slots (ragged padding, threshold
    stop) are already at/below the bound.  Halving stops when the
    remaining merges fit the current size, the half would drop below
    ``min_stage``, or it would break ``align`` (kernel lane multiples,
    shard row counts).  The plan depends only on static values, so the
    whole schedule traces into one compiled program.
    """
    if align < 1:
        raise ValueError(f"align must be >= 1, got {align}")
    stages: list[tuple[int, int]] = []
    size, remaining = n, max(n_steps, 0)
    while True:
        boundary = size - size // 2        # merges that guarantee live <= half
        half = size // 2
        if remaining <= boundary or half < max(min_stage, 2) or half % align:
            stages.append((size, remaining))
            return tuple(stages)
        stages.append((size, boundary))
        remaining -= boundary
        size = half


def resolve_compaction(
    flag,
    n: int,
    n_steps: int,
    *,
    min_stage: int = MIN_STAGE_N,
    align: int = 1,
) -> bool:
    """Canonical compaction switch for a run/signature.

    ``flag`` is the user knob (``True`` / ``False`` / ``"auto"``);
    ``"auto"`` and ``True`` both resolve to ``False`` whenever the stage
    plan degenerates to a single stage (tiny ``n``, aggressive
    ``stop_at_k``, alignment floor) so a no-op schedule never forks a
    separate compile — signatures stay canonical.
    """
    if flag in (False, None, "off"):
        return False
    if flag not in (True, "auto", "on"):
        raise ValueError(
            f"compaction must be a bool or 'auto', got {flag!r}"
        )
    return len(plan_stages(n, n_steps, min_stage=min_stage, align=align)) > 1


def _live_perm(alive: jax.Array, half: int):
    """The compaction permutation: live slots packed ascending.

    Ascending order is load-bearing — it preserves the live slots'
    *relative* order, the thing row-major first-minimum tie-breaking
    keys on, so the merge sequence is unchanged by compaction.  Every
    backend's gather pass MUST build its permutation here.  Returns
    ``(live, pc)``: the new liveness mask and the clipped gather index
    (dead tail slots point at row ``n - 1``; callers mask them).
    """
    n = alive.shape[-1]
    perm = jnp.sort(jnp.where(alive, jnp.arange(n), n))[:half]
    return perm < n, jnp.minimum(perm, n - 1).astype(jnp.int32)


def compact_dense(
    D: jax.Array,
    alive: jax.Array,
    sizes: jax.Array,
    remap: jax.Array,
    half: int,
):
    """One gather pass: pack live rows/cols into a ``(half, half)`` matrix.

    Returns ``(D', alive', sizes', remap')`` where ``remap'[s]`` is the
    original slot id of compacted slot ``s`` (monotone over live slots —
    the :func:`_live_perm` invariant — so ``i < j`` keeps meaning
    ``remap[i] < remap[j]``).  Works for both storage representations:
    values of live cells are copied untouched and the new dead tail is
    re-premasked to ``+inf``.
    """
    live, p = _live_perm(alive, half)
    Dn = premask(D[p][:, p], live)
    return Dn, live, jnp.where(live, sizes[p], 0.0), remap[p]


def staged_merge_loop(
    stages,
    state: "LWState",
    remap: jax.Array,
    threshold,
    *,
    ops_for: Callable[[int], "StepOps"],
    compact: Callable,
    cache_for: Callable[[int], tuple],
) -> "LWState":
    """The ONE staged-loop driver every backend composition runs.

    Per stage: (after the first) ``compact(state, remap, size)`` packs
    the live slots and the stage cache is rebuilt at the new size, then
    :func:`run_merge_loop` runs the stage's trips, then the recorded
    merges are rewritten to original slot ids.  A single-stage plan is
    exactly the pre-compaction loop — no gather, no remap.
    """
    start = 0
    for si, (size, steps) in enumerate(stages):
        if si > 0:
            D, alive, sizes, remap = compact(state, remap, size)
            state = LWState(
                D=D, alive=alive, sizes=sizes,
                merges=state.merges, n_merges=state.n_merges,
                cand=state.cand, cache=cache_for(size),
            )
        state = run_merge_loop(
            ops_for(size), state, start + steps, threshold, start=start
        )
        if si > 0:
            state = state._replace(
                merges=remap_merges(
                    state.merges, state.n_merges, remap, start, steps
                )
            )
        start += steps
    return state


def remap_merges(
    merges: jax.Array,
    n_merges: jax.Array,
    remap: jax.Array,
    start: int,
    steps: int,
) -> jax.Array:
    """Rewrite one stage's merge rows from compacted slots to original ids.

    Only rows actually recorded (``< n_merges``) are rewritten — rows a
    threshold stop never reached keep their all-zero contract.  ``remap``
    is monotone over live slots, so the rewritten ``(i, j)`` keep
    ``i < j`` with slot ``i`` holding the union.
    """
    if steps <= 0:
        return merges
    seg = merges[start : start + steps]
    ij = jnp.clip(seg[:, :2].astype(jnp.int32), 0, remap.shape[0] - 1)
    mapped = remap[ij].astype(_F32)
    valid = (jnp.arange(start, start + steps) < n_merges)[:, None]
    return merges.at[start : start + steps, :2].set(
        jnp.where(valid, mapped, seg[:, :2])
    )


class LWResult(NamedTuple):
    """Output of a Lance-Williams run.

    merges: ``(n_steps, 4)`` float32 — rows ``(i, j, dist, new_size)``
        where ``i < j`` are the *slot* indices merged at that step (slot
        ``i`` keeps the union).  ``n_steps`` is ``n - 1`` for a full run,
        ``n - stop_at_k`` for an early-stopped one.  Use
        :mod:`repro.core.dendrogram` to convert to a scipy-style linkage
        matrix or flat cluster labels.
    n_merges: scalar int32 — merges actually recorded.  Equals
        ``n_steps`` unless ``distance_threshold`` stopped the run early;
        rows past ``n_merges`` are zero.
    """

    merges: jax.Array
    n_merges: jax.Array


class LWState(NamedTuple):
    """Carry of the merge loop — every backend runs exactly this state.

    ``D`` is the distance storage in the backend's representation: the
    dense ``(n, n)`` matrix (premasked or garbage) or the local
    ``(rows, n)`` block of a row-sharded matrix.  ``cand`` is the next
    merge candidate ``(r, c, dmin)`` produced by the argmin op (computed
    at the tail of each step so the reduction fuses with the update
    pass's producer).  ``cache`` is argmin-op-owned state — ``()`` for
    the baseline op, ``(rmin, rarg)`` for ``rowmin``/``lazy``.
    """

    D: jax.Array
    alive: jax.Array
    sizes: jax.Array
    merges: jax.Array
    n_merges: jax.Array
    cand: tuple[jax.Array, jax.Array, jax.Array]
    cache: tuple


class StepOps(NamedTuple):
    """The pluggable primitives a step is assembled from.

    seed:    fill ``cand`` (+ ``cache``) from the initial state.
    fetch:   ``(state, i, j) -> (d_ki, d_kj)`` — the two rows the
             recurrence consumes (dense column reads, or the paper's
             owner-contributes ``psum`` broadcast).
    update:  ``(d_ki, d_kj, d_ij, n_i, n_j, sizes, keep) -> new`` —
             the LW recurrence over a whole row, dead lanes filled with
             the representation's tombstone value.
    write:   ``(state, i, j, new) -> D`` — commit the merged row.
    refresh: recompute ``cand`` (+ ``cache``) after a merge; reads the
             just-applied ``(i, j)`` from ``state.cand``.
    commit:  optional **fused one-pass step tail** replacing
             update→write→refresh:
             ``(state, i, j, dmin, d_ki, d_kj, keep, alive_next)
             -> (D, cand, cache)`` applies the LW recurrence, commits
             the merged row AND computes the next step's row minima in
             the same matrix pass — the separate argmin-tail and update
             passes collapse into one, roughly halving per-step matrix
             traffic (one Pallas ``lw_step`` launch on the kernel
             backend; one XLA fusion region on the jnp backends).  Must
             produce values identical to the unfused three-step sequence.
    """

    seed: Callable[[LWState], LWState]
    fetch: Callable[[LWState, jax.Array, jax.Array], tuple[jax.Array, jax.Array]]
    update: Callable[..., jax.Array]
    write: Callable[[LWState, jax.Array, jax.Array, jax.Array], jax.Array]
    refresh: Callable[[LWState], LWState]
    commit: Callable[..., tuple] | None = None


def symmetrize(D: jax.Array) -> jax.Array:
    """The single input-normalization path (every backend routes here).

    Accepts a full symmetric matrix or just the upper triangle (per
    problem for batched ``(..., n, n)`` input), averages ``D`` with its
    transpose and zeroes the diagonal.  Padding cells stay zero.
    """
    D = jnp.asarray(D, _F32)
    n = D.shape[-1]
    if D.ndim < 2 or D.shape[-2] != n:
        raise ValueError(f"distance matrix must be square, got {D.shape}")
    eye = jnp.eye(n, dtype=bool)
    upper = jnp.triu(D, k=1)
    has_lower = jnp.any(jnp.tril(D, k=-1) != 0, axis=(-2, -1), keepdims=True)
    full_sym = jnp.where(has_lower, D, upper + jnp.swapaxes(upper, -2, -1))
    return jnp.where(eye, 0.0, 0.5 * (full_sym + jnp.swapaxes(full_sym, -2, -1)))


def resolve_n_steps(n: int, stop_at_k: int) -> int:
    """Merge count for a run over ``n`` items stopping at ``k`` clusters."""
    if stop_at_k < 1:
        raise ValueError(f"stop_at_k must be >= 1, got {stop_at_k}")
    return max(n - stop_at_k, 0)


# ---------------------------------------------------------------------------
# the ONE step + the ONE loop
# ---------------------------------------------------------------------------


def make_step(ops: StepOps) -> Callable[..., LWState]:
    """Assemble the paper's merge step from primitives.

    This is the only implementation of the LW merge iteration in the
    repo: candidate → recurrence → commit → tombstone → record →
    refresh.  Bookkeeping uses fused iota-mask selects (not scatters) so
    the same code is fast under jit, vmap and shard_map alike.

    ``t`` is the merge-record index.  The fixed-trip loop passes its
    induction variable (equal to ``n_merges`` but *unbatched* under
    vmap, so the record write stays a dynamic-update-slice rather than a
    per-lane scatter); the threshold loop passes nothing and the
    per-lane counter is used.
    """

    def step(s: LWState, t: jax.Array | None = None) -> LWState:
        r, c, dmin = s.cand
        i, j = jnp.minimum(r, c), jnp.maximum(r, c)  # slot i keeps the union

        d_ki, d_kj = ops.fetch(s, i, j)
        ks = jnp.arange(s.alive.shape[0])
        keep = s.alive & (ks != i) & (ks != j)

        is_i, is_j = ks == i, ks == j
        new_size = s.sizes[i] + s.sizes[j]
        alive = s.alive & ~is_j
        sizes = jnp.where(is_i, new_size, jnp.where(is_j, 0.0, s.sizes))
        merges = s.merges.at[s.n_merges if t is None else t].set(
            jnp.stack([i.astype(_F32), j.astype(_F32), dmin, new_size])
        )
        if ops.commit is not None:
            # fused tail: recurrence + commit + next row minima in ONE
            # matrix pass (and so a threshold loop can still decide
            # *before* applying the next merge)
            D, cand, cache = ops.commit(s, i, j, dmin, d_ki, d_kj, keep, alive)
            return LWState(D, alive, sizes, merges, s.n_merges + 1, cand, cache)
        new = ops.update(d_ki, d_kj, dmin, s.sizes[i], s.sizes[j], s.sizes, keep)
        D = ops.write(s, i, j, new)
        s = LWState(D, alive, sizes, merges, s.n_merges + 1, s.cand, s.cache)
        # next candidate, computed off the freshly written matrix so the
        # reduction fuses with the update pass (and so a threshold loop
        # can decide *before* applying the next merge)
        return ops.refresh(s)

    return step


def run_merge_loop(
    ops: StepOps,
    state: LWState,
    n_steps: int,
    distance_threshold: jax.Array | float | None,
    *,
    start: int = 0,
) -> LWState:
    """Seed the candidate, then run merge trips ``[start, n_steps)``.

    Without a threshold the loop is a fixed-trip ``fori_loop`` (shapes
    static, zero per-step guards).  With one it is a ``while_loop`` that
    exits before the first merge whose distance exceeds the threshold —
    a genuine trip-count reduction, not a masked no-op.  Only the
    None-vs-set distinction is structural; the threshold *value* may be
    a traced scalar, so callers jit it as an operand (distinct dedup
    radii must not recompile the loop).

    ``start`` is the global trip index this call resumes at (a compaction
    stage boundary); ``state.n_merges`` equals it when the run is still
    live.  Under a threshold, a stage whose predecessor stopped early
    (``n_merges < start``) runs zero trips — the stop is permanent.
    """
    if n_steps <= start:   # stop_at_k >= n: nothing to merge, nothing to trace
        return state
    make = make_step(ops)

    def step(s, t=None):
        return match_vma(make(s, t))

    state = match_vma(ops.seed(state))
    if distance_threshold is None:
        return jax.lax.fori_loop(start, n_steps, lambda t, s: step(s, t), state)
    thr = jnp.asarray(distance_threshold, _F32)

    def cond(s: LWState):
        live = (s.n_merges < n_steps) & (s.cand[2] <= thr)
        if start > 0:
            live &= s.n_merges >= start
        return live

    return jax.lax.while_loop(cond, step, state)


def _init_state(D: jax.Array, alive: jax.Array, n_steps: int, cache: tuple) -> LWState:
    zero = jnp.zeros((), jnp.int32)
    return LWState(
        D=D,
        alive=alive,
        sizes=alive.astype(_F32),
        merges=jnp.zeros((n_steps, 4), _F32),
        n_merges=zero,
        cand=(zero, zero, jnp.zeros((), _F32)),
        cache=cache,
    )


# ---------------------------------------------------------------------------
# dense primitives (serial / vmap / shard_map-over-problems backends)
# ---------------------------------------------------------------------------


def _first_where(mask: jax.Array, ks: jax.Array, n: int) -> jax.Array:
    """Smallest index with ``mask`` true (``n`` when none) — vectorized."""
    return jnp.min(jnp.where(mask, ks, n))


def _row_major_first_min(Dm: jax.Array, ks: jax.Array):
    """(r, c, min) with ``jnp.argmin``'s exact tie-breaking via vector min.

    A vectorized row-min reduce plus first-row / first-col recovery —
    avoids XLA:CPU's scalarized variadic (value, index) reduce while
    reproducing row-major first-minimum bit-exactly.
    """
    n = Dm.shape[0]
    rowmin = jnp.min(Dm, axis=1)
    m = jnp.min(rowmin)
    r = _first_where(rowmin == m, ks, n)
    c = _first_where(Dm[r, :] == m, ks, n)
    return r, c, m


def _row_mins_with_args(Dm: jax.Array, ks: jax.Array):
    """Per-row (min, first-col argmin) of a premasked matrix, vectorized."""
    n = Dm.shape[0]
    rm = jnp.min(Dm, axis=1)
    ra = jnp.min(jnp.where(Dm == rm[:, None], ks[None, :], n), axis=1)
    return rm, ra


def _cached_cand(s: LWState, ks: jax.Array) -> tuple:
    """Global row-major first-min from exact (rmin, rarg) caches."""
    n = s.alive.shape[0]
    rmin, rarg = s.cache
    rvals = jnp.where(s.alive, rmin, _INF)
    m = jnp.min(rvals)
    r = _first_where(rvals == m, ks, n)
    return r, rarg[r], m


def _cache_invalidate(cache: tuple, i: jax.Array, j: jax.Array,
                      new_col: jax.Array, row_ids: jax.Array,
                      alive_rows: jax.Array):
    """The ONE rowmin/lazy cache-maintenance algebra, dense and sharded.

    The rewritten column ``i`` can only *lower* a cached row minimum in
    place (exactly, including first-col tie-breaking: on an equal value
    the smaller column index wins).  Rows whose cached argmin pointed
    into the merged slots — plus row ``i`` itself, rewritten wholesale —
    are stale and must rescan.  ``new_col`` / ``row_ids`` /
    ``alive_rows`` cover the caller's row set: all ``n`` rows for the
    dense primitives, the shard's local block (global ids ``offset + k``)
    for the sharded ones.  Returns ``(rmin, rarg, stale)``.
    """
    rmin, rarg = cache
    lower = (new_col < rmin) | ((new_col == rmin) & (i < rarg))
    lower = lower & (row_ids != i) & (row_ids != j)
    rmin = jnp.where(lower, new_col, rmin)
    rarg = jnp.where(lower, i, rarg)
    stale = ((rarg == i) | (rarg == j) | (row_ids == i)) & ~lower & alive_rows
    return rmin, rarg, stale


def _drain_cache(rmin, rarg, dirty, rescan_rows, K: int):
    """The ONE bounded dirty-row drain of the ``lazy`` variant.

    A ``while_loop`` re-scans at most ``K`` dirty rows per trip
    (``top_k`` picks → caller's ``rescan_rows(picks)`` → scatter back).
    Shared by the dense and sharded primitives.
    """

    def cond(st):
        return jnp.any(st[2])

    def body(st):
        rmin, rarg, dirty = st
        picks = jax.lax.top_k(dirty.astype(_F32), K)[1]
        rm, ra = rescan_rows(picks)
        sel = dirty[picks]
        rmin = rmin.at[picks].set(jnp.where(sel, rm, rmin[picks]))
        rarg = rarg.at[picks].set(jnp.where(sel, ra, rarg[picks]))
        return match_vma((rmin, rarg, dirty.at[picks].set(False)))

    rmin, rarg, _ = jax.lax.while_loop(
        cond, body, match_vma((rmin, rarg, dirty))
    )
    return rmin, rarg


def dense_ops(method: str, n: int, variant: str, *, fused: bool = True) -> StepOps:
    """Primitives for the premasked dense representation (pure jnp).

    Powers the serial backend and — under the vmap / shard_map-over-
    problems wrappers — both batched jnp engines.  For the ``baseline``
    and ``rowmin`` argmin ops the step tail is the fused one-pass
    ``commit``: the recurrence, the row/col commit and the next step's
    row minima live in one function, so XLA emits a single fusion region
    over the matrix instead of a write pass chased by an argmin pass.
    The arithmetic (and therefore the merge list) is identical to the
    unfused sequence; ``fused=False`` keeps the three-primitive tail for
    A/B measurement.  ``lazy`` always stays unfused — its bounded
    dirty-row drain is a data-dependent inner ``while_loop`` that cannot
    join the matrix pass.
    """
    ks = jnp.arange(n)

    def update(d_ki, d_kj, d_ij, n_i, n_j, sizes, keep):
        new = update_row(method, d_ki, d_kj, d_ij, n_i, n_j, sizes)
        return jnp.where(keep, new, _INF)      # premask: dead lanes hold +inf

    def fetch(s, i, j):
        return s.D[:, i], s.D[:, j]

    def write(s, i, j, new):
        # row/col i ← new, row/col j ← +inf, one fused select pass
        is_i, is_j = ks == i, ks == j
        return jnp.where(
            is_j[:, None] | is_j[None, :],
            _INF,
            jnp.where(
                is_i[:, None],
                new[None, :],
                jnp.where(is_i[None, :], new[:, None], s.D),
            ),
        )

    commit = None
    if variant == "baseline":

        def seed(s):
            return s._replace(cand=_row_major_first_min(s.D, ks))

        refresh = seed

        if fused:

            def commit(s, i, j, dmin, d_ki, d_kj, keep, alive_next):
                new = update(d_ki, d_kj, dmin, s.sizes[i], s.sizes[j],
                             s.sizes, keep)
                D = write(s, i, j, new)
                return D, _row_major_first_min(D, ks), ()

    elif variant == "rowmin":

        def seed(s):
            rm, ra = _row_mins_with_args(s.D, ks)
            s = s._replace(cache=(rm, ra))
            return s._replace(cand=_cached_cand(s, ks))

        def refresh(s):
            r, c, _ = s.cand
            i, j = jnp.minimum(r, c), jnp.maximum(r, c)
            rmin, rarg, stale = _cache_invalidate(
                s.cache, i, j, s.D[:, i], ks, s.alive
            )
            full_rm, full_ra = _row_mins_with_args(s.D, ks)
            s = s._replace(
                cache=(
                    jnp.where(stale, full_rm, rmin),
                    jnp.where(stale, full_ra, rarg),
                )
            )
            return s._replace(cand=_cached_cand(s, ks))

        if fused:

            def commit(s, i, j, dmin, d_ki, d_kj, keep, alive_next):
                new = update(d_ki, d_kj, dmin, s.sizes[i], s.sizes[j],
                             s.sizes, keep)
                D = write(s, i, j, new)
                # the freshly written column i IS ``new`` (rows i/j hold
                # its +inf tombstones), so the invalidation algebra needs
                # no column re-gather; the stale-row rescan reads D in
                # the same pass that produced it.
                rmin, rarg, stale = _cache_invalidate(
                    s.cache, i, j, new, ks, alive_next
                )
                full_rm, full_ra = _row_mins_with_args(D, ks)
                cache = (
                    jnp.where(stale, full_rm, rmin),
                    jnp.where(stale, full_ra, rarg),
                )
                s2 = s._replace(D=D, alive=alive_next, cache=cache)
                return D, _cached_cand(s2, ks), cache

    elif variant == "lazy":
        K = min(LAZY_BATCH_K, n)

        def rescan_rows(D, picks):
            sub = jnp.take(D, picks, axis=0)          # (K, n) premasked
            rm = jnp.min(sub, axis=1)
            ra = jnp.min(jnp.where(sub == rm[:, None], ks[None, :], n), axis=1)
            return rm, ra

        def seed(s):
            rm, ra = _row_mins_with_args(s.D, ks)
            s = s._replace(cache=(rm, ra))
            return s._replace(cand=_cached_cand(s, ks))

        def refresh(s):
            r, c, _ = s.cand
            i, j = jnp.minimum(r, c), jnp.maximum(r, c)
            rmin, rarg, dirty = _cache_invalidate(
                s.cache, i, j, s.D[:, i], ks, s.alive
            )
            cache = _drain_cache(
                rmin, rarg, dirty, lambda picks: rescan_rows(s.D, picks), K
            )
            s = s._replace(cache=cache)
            return s._replace(cand=_cached_cand(s, ks))

    else:
        raise ValueError(f"unknown variant {variant!r}; pick from {VARIANTS}")

    return StepOps(seed=seed, fetch=fetch, update=update, write=write,
                   refresh=refresh, commit=commit)


def premask(D: jax.Array, alive: jax.Array) -> jax.Array:
    """Apply the liveness/diagonal mask once, up front (dense paths)."""
    n = D.shape[-1]
    eye = jnp.eye(n, dtype=bool)
    valid = alive[..., :, None] & alive[..., None, :] & ~eye
    return jnp.where(valid, D, _INF)


def run_dense(
    D: jax.Array,
    alive: jax.Array,
    *,
    method: str,
    n_steps: int,
    variant: str = "baseline",
    distance_threshold: jax.Array | float | None = None,
    compaction: bool = False,
) -> LWResult:
    """fori/while-loop wrapper over the dense premasked primitives.

    ``D`` is one prepared ``(n, n)`` matrix; slots with ``alive=False``
    are dead from birth (ragged padding).  vmap this function over a
    leading batch axis for the batched engines — every primitive is
    rank-polymorphic under batching (including the compaction gather).

    With ``compaction`` the run follows :func:`plan_stages`: each stage
    boundary packs the live rows/cols into the half-size matrix
    (:func:`compact_dense`) and the recorded stage merges are rewritten
    to original slot ids (:func:`remap_merges`) — output is bit-identical
    to the single-stage run, the matrix passes just stop touching dead
    rows.
    """
    n = D.shape[-1]
    stages = (
        plan_stages(n, n_steps) if compaction else ((n, n_steps),)
    )
    out = staged_merge_loop(
        stages,
        _init_state(premask(D, alive), alive, n_steps,
                    _dense_cache(n, variant)),
        jnp.arange(n, dtype=jnp.int32),
        distance_threshold,
        ops_for=lambda size: dense_ops(method, size, variant),
        compact=lambda s, remap, size: compact_dense(
            s.D, s.alive, s.sizes, remap, size
        ),
        cache_for=lambda size: _dense_cache(size, variant),
    )
    return LWResult(merges=out.merges, n_merges=out.n_merges)


def _dense_cache(n: int, variant: str) -> tuple:
    """Structural cache placeholder (seeded before the loop runs)."""
    if variant == "baseline":
        return ()
    return (jnp.zeros((n,), _F32), jnp.zeros((n,), jnp.int32))


# ---------------------------------------------------------------------------
# kernel primitives (Pallas min-scan argmin + Pallas lw_update)
# ---------------------------------------------------------------------------


def kernel_ops(
    method: str,
    n: int,
    variant: str,
    *,
    block_m: int,
    interpret: bool,
    fused: bool = True,
) -> StepOps:
    """Primitives routing step 1 / step 6b through the Pallas kernels.

    Garbage representation: dead cells hold inert values and the
    ``alive`` mask is applied at argmin time (in VMEM for the min-scans;
    in the jnp masked view for the cached variants).  Batched execution
    needs no dedicated kernels — under ``vmap`` the ``pallas_call``
    batching rule prepends the batch as a leading grid dimension, which
    is exactly the hand-scheduled ``grid=(B, slabs)`` layout.

    With ``fused`` (the default) the ``baseline``/``rowmin`` step tail
    is ONE :func:`repro.kernels.lw_step.lw_step_pallas` launch — the LW
    update, the row/col commit and the next step's row minima in the
    same VMEM pass — instead of an ``lw_update`` launch, a jnp select
    pass and a ``minscan`` launch.  ``lazy`` keeps the unfused tail (its
    bounded drain is a data-dependent inner loop).
    """
    from repro.kernels.lw_update import lw_update_pallas
    from repro.kernels.minscan import masked_argmin_pallas
    from repro.kernels.lw_step import lw_step_pallas

    ks = jnp.arange(n)

    def update(d_ki, d_kj, d_ij, n_i, n_j, sizes, keep):
        return lw_update_pallas(
            method, d_ki, d_kj, d_ij, n_i, n_j, sizes,
            keep.astype(_F32), block_n=min(2048, n), interpret=interpret,
        )

    def fetch(s, i, j):
        return s.D[:, i], s.D[:, j]

    def write(s, i, j, new):
        # row/col i ← new (new[i] == 0 keeps the diagonal), row/col j stay
        # as garbage — the argmin ops mask them out via ``alive``
        is_i = ks == i
        return jnp.where(
            is_i[:, None],
            new[None, :],
            jnp.where(is_i[None, :], new[:, None], s.D),
        )

    def masked_view(s):
        return premask(s.D, s.alive)

    commit = None
    if fused and variant in ("baseline", "rowmin"):
        # the fused kernel recomputes exact row minima every step, so a
        # rowmin cache would be write-only dead carry — both variants run
        # cache-free and are identical by construction on this path
        # (see kernel_cache).

        def commit(s, i, j, dmin, d_ki, d_kj, keep, alive_next):
            D, rmin, rarg = lw_step_pallas(
                method, s.D, d_ki, d_kj, dmin, s.sizes[i], s.sizes[j],
                s.sizes, s.alive.astype(_F32), i, j,
                block_m=block_m, interpret=interpret,
            )
            # global candidate from the kernel's per-row minima — the
            # same row-major first-minimum the min-scan kernel emits
            m = jnp.min(rmin)
            r = _first_where(rmin == m, ks, n)
            return D, (r, rarg[r], m), ()

    if variant == "baseline" or commit is not None:

        def seed(s):
            v, flat = masked_argmin_pallas(
                s.D, s.alive.astype(_F32), block_m=block_m, interpret=interpret
            )
            return s._replace(cand=(flat // n, flat % n, v))

        refresh = seed

    elif variant in ("rowmin", "lazy"):
        # cached row minima in jnp over the masked view; the Pallas
        # min-scan's row-major tie-breaking is reproduced exactly, so the
        # variant stays index-identical to the kernel baseline.
        dense = dense_ops(method, n, variant, fused=False)

        def seed(s):
            return dense.seed(s._replace(D=masked_view(s)))._replace(D=s.D)

        def refresh(s):
            return dense.refresh(s._replace(D=masked_view(s)))._replace(D=s.D)

    else:
        raise ValueError(f"unknown variant {variant!r}; pick from {VARIANTS}")

    return StepOps(seed=seed, fetch=fetch, update=update, write=write,
                   refresh=refresh, commit=commit)


def kernel_cache(n: int, variant: str, *, fused: bool = True) -> tuple:
    """Loop-carry cache structure of the kernel composition.

    Must mirror :func:`kernel_ops`: the fused ``lw_step`` path runs
    ``baseline``/``rowmin`` cache-free (its per-step recomputed row
    minima ARE the argmin), only ``lazy`` — and the unfused cached
    variants — carry ``(rmin, rarg)``.
    """
    if fused and variant != "lazy":
        return _dense_cache(n, "baseline")
    return _dense_cache(n, variant)


#: Stage floor of the kernel compaction plan — stage sizes must stay
#: TPU lane multiples so every in-loop ``pallas_call`` stays aligned.
KERNEL_STAGE_ALIGN = 128


def run_kernel(
    D: jax.Array,
    alive: jax.Array,
    *,
    method: str,
    n_steps: int,
    variant: str = "baseline",
    distance_threshold: jax.Array | float | None = None,
    block_m: int = 256,
    interpret: bool = False,
    compaction: bool = False,
) -> LWResult:
    """Loop wrapper over the kernel primitives (lane-aligned ``D``).

    Compaction stages halve only down to :data:`KERNEL_STAGE_ALIGN` (the
    lane multiple every kernel launch requires); the gather pass is the
    same :func:`compact_dense` the jnp paths use — the premasked values
    it writes are inert under the kernels' at-argmin-time masking.
    """
    n = D.shape[-1]
    stages = (
        plan_stages(n, n_steps, min_stage=KERNEL_STAGE_ALIGN,
                    align=KERNEL_STAGE_ALIGN)
        if compaction
        else ((n, n_steps),)
    )

    def ops_for(size: int) -> StepOps:
        bm = block_m if size % block_m == 0 else KERNEL_STAGE_ALIGN
        return kernel_ops(method, size, variant, block_m=bm,
                          interpret=interpret)

    out = staged_merge_loop(
        stages,
        _init_state(D, alive, n_steps, kernel_cache(n, variant)),
        jnp.arange(n, dtype=jnp.int32),
        distance_threshold,
        ops_for=ops_for,
        compact=lambda s, remap, size: compact_dense(
            s.D, s.alive, s.sizes, remap, size
        ),
        cache_for=lambda size: kernel_cache(size, variant),
    )
    return LWResult(merges=out.merges, n_merges=out.n_merges)


# ---------------------------------------------------------------------------
# sharded primitives (shard_map over matrix rows — the paper's §5.3)
# ---------------------------------------------------------------------------


def make_sharded_body(
    method: str,
    n_steps: int,
    variant: str = "baseline",
    with_threshold: bool = False,
    compaction: bool = False,
):
    """Per-shard merge-loop body for ``shard_map`` over matrix rows.

    Runs the same :func:`make_step` skeleton with collective primitives:
    step 1 is a local masked min + ``all_gather`` of the per-shard
    ``(lmin, r, c)`` triples (every shard replicates the global argmin —
    the paper's "no further communication" observation), the row fetch is
    an owner-contributes ``psum`` broadcast, and the write commits each
    shard's slice of column ``i`` plus the owner's row ``i``.  The
    ``rowmin``/``lazy`` argmin variants keep their caches shard-local.

    The body takes the distance threshold as a replicated *operand*
    (ignored unless ``with_threshold``) so distinct thresholds reuse one
    compile; the exit condition reads only replicated values, keeping
    every shard's collectives aligned.

    With ``compaction`` the body runs the :func:`plan_stages` schedule:
    at each stage boundary every shard computes the (replicated) live
    permutation, contributes the old rows it owns with one ``psum``
    (O(n²/2p) bytes — the collective form of a re-shard), and keeps its
    new ``size/2p``-row block — per-device storage *shrinks with the
    run*, extending the paper's n²/p claim downward as merges retire
    rows.  Stage sizes stay multiples of the shard count.
    """

    def body(
        D_local: jax.Array,
        alive0: jax.Array,
        sizes0: jax.Array,
        threshold: jax.Array,
    ):
        rows0, n_pad0 = D_local.shape
        p = n_pad0 // rows0
        stages = (
            plan_stages(n_pad0, n_steps, align=p)
            if compaction
            else ((n_pad0, n_steps),)
        )

        def build_ops(rows: int, n_pad: int) -> StepOps:
            """The collective primitives for one stage's block shape."""
            offset = jax.lax.axis_index(AXIS) * rows
            row_ids = offset + jnp.arange(rows)
            cols = jnp.arange(n_pad)

            def local_mask(D_local, alive):
                valid = (
                    alive[row_ids][:, None]
                    & alive[None, :]
                    & (row_ids[:, None] != cols[None, :])
                )
                return jnp.where(valid, D_local, _INF)

            def elect(lmin, lr_global, lc):
                """all-gather the shard candidates, replicate the argmin."""
                trip = jnp.stack([lmin, lr_global.astype(_F32), lc.astype(_F32)])
                allt = jax.lax.all_gather(trip, AXIS)  # (p, 3) — replicated
                w = jnp.argmin(allt[:, 0])             # first shard wins ties
                return (
                    allt[w, 1].astype(jnp.int32),
                    allt[w, 2].astype(jnp.int32),
                    allt[w, 0],
                )

            def update(d_ki, d_kj, d_ij, n_i, n_j, sizes, keep):
                new = update_row(method, d_ki, d_kj, d_ij, n_i, n_j, sizes)
                return jnp.where(keep, new, 0.0)       # garbage rep: dead = 0

            def fetch(s, i, j):
                def take_row(g):
                    mine = (g >= offset) & (g < offset + rows)
                    lrow = jnp.clip(g - offset, 0, rows - 1)
                    return jnp.where(mine, s.D[lrow, :], 0.0)

                rows_ij = jax.lax.psum(
                    jnp.stack([take_row(i), take_row(j)]), AXIS
                )                                      # (2, n_pad) — O(2n) bytes
                return rows_ij[0], rows_ij[1]

            def write(s, i, j, new):
                D_local = s.D.at[:, i].set(
                    jax.lax.dynamic_slice(new, (offset,), (rows,))
                )
                own = (i >= offset) & (i < offset + rows)
                li = jnp.clip(i - offset, 0, rows - 1)
                D_own = D_local.at[li, :].set(new).at[li, i].set(0.0)
                return jnp.where(own, D_own, D_local)

            if variant == "baseline":

                def seed(s):
                    Dm = local_mask(s.D, s.alive)
                    flat = jnp.argmin(Dm)              # local row-major first-min
                    lr, lc = flat // n_pad, flat % n_pad
                    return s._replace(cand=elect(Dm[lr, lc], offset + lr, lc))

                refresh = seed

            elif variant in ("rowmin", "lazy"):

                def local_cand(s):
                    rmin, rarg = s.cache
                    rvals = jnp.where(s.alive[row_ids], rmin, _INF)
                    lr = jnp.argmin(rvals)
                    return s._replace(cand=elect(rvals[lr], offset + lr, rarg[lr]))

                def full_rescan(s):
                    Dm = local_mask(s.D, s.alive)
                    rm = jnp.min(Dm, axis=1)
                    ra = jnp.min(
                        jnp.where(Dm == rm[:, None], cols[None, :], n_pad), axis=1
                    )
                    return rm, ra

                def seed(s):
                    return local_cand(s._replace(cache=full_rescan(s)))

                def invalidate(s):
                    """The shared cache algebra over this shard's row block."""
                    r, c, _ = s.cand
                    i, j = jnp.minimum(r, c), jnp.maximum(r, c)
                    return _cache_invalidate(
                        s.cache, i, j, s.D[:, i], row_ids, s.alive[row_ids]
                    )

                if variant == "rowmin":

                    def refresh(s):
                        rmin, rarg, stale = invalidate(s)
                        full_rm, full_ra = full_rescan(s)
                        cache = (
                            jnp.where(stale, full_rm, rmin),
                            jnp.where(stale, full_ra, rarg),
                        )
                        return local_cand(s._replace(cache=cache))

                else:                                  # lazy: bounded drain
                    K = min(LAZY_BATCH_K, rows)

                    def rescan_rows(s, picks):
                        sub = jnp.take(s.D, picks, axis=0)       # (K, n_pad)
                        gids = row_ids[picks]
                        valid = (
                            s.alive[gids][:, None]
                            & s.alive[None, :]
                            & (gids[:, None] != cols[None, :])
                        )
                        sub = jnp.where(valid, sub, _INF)
                        rm = jnp.min(sub, axis=1)
                        ra = jnp.min(
                            jnp.where(sub == rm[:, None], cols[None, :], n_pad),
                            axis=1,
                        )
                        return rm, ra

                    def refresh(s):
                        rmin, rarg, dirty = invalidate(s)
                        cache = _drain_cache(
                            rmin, rarg, dirty,
                            lambda picks: rescan_rows(s, picks), K,
                        )
                        return local_cand(s._replace(cache=cache))

            else:
                raise ValueError(
                    f"unknown variant {variant!r}; pick from {VARIANTS}"
                )

            return StepOps(seed=seed, fetch=fetch, update=update, write=write,
                           refresh=refresh)

        def compact_sharded(s: LWState, remap, half: int):
            """Re-shard the live slots into ``half/p``-row blocks.

            The permutation is computed from the replicated ``alive``
            mask (identical on every shard); each shard contributes the
            old rows it owns for EVERY new row, and one reduce-scatter
            (``psum_scatter``) both sums the contributions and hands
            each shard exactly its new block — O(size²/2p) received
            bytes per device, the collective form of a re-shard.  The
            live-column gather is then local."""
            rows_old, n_old = s.D.shape
            live, pc = _live_perm(s.alive, half)

            offset_old = jax.lax.axis_index(AXIS) * rows_old
            mine = (pc >= offset_old) & (pc < offset_old + rows_old) & live
            lidx = jnp.clip(pc - offset_old, 0, rows_old - 1)
            contrib = jnp.where(mine[:, None], s.D[lidx, :], 0.0)
            block = jax.lax.psum_scatter(
                contrib, AXIS, scatter_dimension=0, tiled=True
            )                                          # (rows_new, n_old)
            D_new = block[:, pc]                       # local column gather
            sizes_new = jnp.where(live, s.sizes[pc], 0.0)
            return D_new, live, sizes_new, remap[pc]

        # the carry mixes shard-varying (D_local, cache) and replicated
        # values; the loop marks everything varying (match_vma) and the
        # epilogue reduces back.
        state = LWState(
            D=D_local,
            alive=alive0,
            sizes=sizes0.astype(_F32),
            merges=jnp.zeros((n_steps, 4), _F32),
            n_merges=jnp.zeros((), jnp.int32),
            cand=(jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
                  jnp.zeros((), _F32)),
            cache=_dense_cache(rows0, variant),   # shard-local row cache
        )
        state = staged_merge_loop(
            stages,
            state,
            jnp.arange(n_pad0, dtype=jnp.int32),
            threshold if with_threshold else None,
            ops_for=lambda size: build_ops(size // p, size),
            compact=compact_sharded,
            cache_for=lambda size: _dense_cache(size // p, variant),
        )
        # every shard computed the identical merge list; pmax re-establishes
        # the replicated type for out_specs=P() (values are bitwise equal).
        return (
            jax.lax.pmax(state.merges, AXIS),
            jax.lax.pmax(state.n_merges, AXIS),
        )

    return body
