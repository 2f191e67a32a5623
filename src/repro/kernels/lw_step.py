"""Pallas TPU kernel: fused one-pass Lance-Williams merge step.

The unfused kernel composition touches the matrix twice per merge: the
``lw_update`` kernel rewrites the merged row, a jnp select pass commits
row/column ``i``, and the ``minscan`` kernel re-scans the whole matrix
for the next candidate.  This kernel collapses the step tail into ONE
``(bm, n)``-slab pass: for each row slab it

1. evaluates the LW recurrence for the merged row (full length, from
   the two fetched columns — the same formula ``lw_update`` fuses),
2. commits column ``i`` (per-row recurrence values) and row ``i`` (the
   full merged row) into the output slab, leaving row/col ``j`` as
   garbage (the representation's tombstone convention), and
3. emits the slab's per-row ``(min, first-col argmin)`` of the *new*
   masked matrix — the next step's row minima — while the slab is still
   in VMEM.

Per-step matrix traffic drops from two full read passes (+ one write)
to one read + one write.  Tie-breaking is row-major first-minimum,
identical to ``minscan`` and the dense engine, so kernel merge indices
stay index-identical.  The merge scalars ``(d_ij, n_i, n_j)`` and slot
indices ``(i, j)`` arrive as ``(1, lanes)`` operands, so one compiled
kernel serves every iteration; under ``jax.vmap`` the ``pallas_call``
batching rule prepends the batch as a leading grid dimension — no
dedicated batched kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.linkage import METHODS, coefficients
from repro.kernels.minscan import row_min_args, slab_rows

_LANES = 128


def _lw(method, d_ki, d_kj, d_ij, n_i, n_j, n_k):
    a_i, a_j, b, g = coefficients(method, n_i, n_j, n_k)
    return a_i * d_ki + a_j * d_kj + b * d_ij + g * jnp.abs(d_ki - d_kj)


def _make_kernel(method: str):
    def kernel(
        d_ref,          # (bm, n)  this row slab of D
        dki_col_ref,    # (1, n)   fetched column i (== row i, symmetric)
        dkj_col_ref,    # (1, n)   fetched column j
        dki_row_ref,    # (bm, 1)  slab-rows slice of column i
        dkj_row_ref,    # (bm, 1)  slab-rows slice of column j
        sizes_col_ref,  # (1, n)   pre-merge cluster sizes
        sizes_row_ref,  # (bm, 1)  slab-rows slice of sizes
        alive_col_ref,  # (1, n)   pre-merge liveness (float)
        alive_row_ref,  # (bm, 1)  slab-rows slice of liveness
        scal_ref,       # (1, lanes) float32: d_ij, n_i, n_j
        idx_ref,        # (1, lanes) int32:   i, j
        out_ref,        # (bm, n)  new slab
        rmin_ref,       # (bm, 1)  per-row min of the new masked matrix
        rarg_ref,       # (bm, 1)  per-row first-col argmin
    ):
        s = pl.program_id(0)
        d = d_ref[...]
        bm, n = d.shape
        d_ij = scal_ref[0, 0]
        n_i = scal_ref[0, 1]
        n_j = scal_ref[0, 2]
        i = idx_ref[0, 0]
        j = idx_ref[0, 1]

        cols = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
        rows = s * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)

        # the merged row, full length (paper step 6b — lw_update's fusion)
        alive_col = alive_col_ref[...] != 0
        keep_col = alive_col & (cols != i) & (cols != j)
        new_full = _lw(method, dki_col_ref[...], dkj_col_ref[...], d_ij,
                       n_i, n_j, sizes_col_ref[...])
        new_full = jnp.where(keep_col, new_full, 0.0)      # garbage rep

        # the same recurrence at this slab's row positions → column i
        alive_row = alive_row_ref[...] != 0
        keep_row = alive_row & (rows != i) & (rows != j)
        new_rows = _lw(method, dki_row_ref[...], dkj_row_ref[...], d_ij,
                       n_i, n_j, sizes_row_ref[...])
        new_rows = jnp.where(keep_row, new_rows, 0.0)      # (bm, 1)

        row_g = s * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, n), 0)
        col_g = jax.lax.broadcasted_iota(jnp.int32, (bm, n), 1)
        out = jnp.where(col_g == i, new_rows, d)
        out = jnp.where(row_g == i, new_full, out)         # row/col j: garbage
        out_ref[...] = out

        # next step's row minima over the just-written slab (step 1 of the
        # NEXT iteration), masked with the post-merge liveness (j dead)
        live_r = alive_row & (rows != j)
        live_c = alive_col & (cols != j)
        valid = live_r & live_c & (row_g != col_g)
        rmin, rarg = row_min_args(jnp.where(valid, out, jnp.inf), col_g, n)
        rmin_ref[...] = rmin
        rarg_ref[...] = rarg

    return kernel


def lw_step_pallas(
    method: str,
    D: jax.Array,
    d_ki: jax.Array,
    d_kj: jax.Array,
    d_ij: jax.Array,
    n_i: jax.Array,
    n_j: jax.Array,
    sizes: jax.Array,
    alive: jax.Array,
    i: jax.Array,
    j: jax.Array,
    *,
    block_m: int = 256,
    interpret: bool = False,
):
    """One fused merge step: commit merge ``(i, j)`` and return the next
    row minima.  Requires square lane-aligned ``D`` with
    ``n % block_m == 0``; the slab height then shrinks to fit VMEM
    (:func:`repro.kernels.minscan.slab_rows`).

    D: ``(n, n)`` float32 (garbage representation);
    d_ki, d_kj: ``(n,)`` fetched columns; sizes: ``(n,)`` pre-merge sizes;
    alive: ``(n,)`` pre-merge liveness (bool/float);
    d_ij, n_i, n_j: scalars; i, j: int32 slot indices (``i < j``).
    Returns ``(D_new, rmin, rarg)`` — the committed matrix plus per-row
    ``(min, first-col argmin)`` of the post-merge masked matrix.
    """
    if method not in METHODS:
        raise ValueError(f"unknown linkage method {method!r}")
    n = D.shape[0]
    assert D.shape == (n, n) and n % block_m == 0, (D.shape, block_m)
    block_m = slab_rows(n, block_m)

    scal = jnp.zeros((1, _LANES), jnp.float32)
    scal = scal.at[0, 0].set(d_ij).at[0, 1].set(n_i).at[0, 2].set(n_j)
    idx = jnp.zeros((1, _LANES), jnp.int32)
    idx = idx.at[0, 0].set(i).at[0, 1].set(j)

    col_spec = pl.BlockSpec((1, n), lambda s: (0, 0))
    row_spec = pl.BlockSpec((block_m, 1), lambda s: (s, 0))
    slab_spec = pl.BlockSpec((block_m, n), lambda s: (s, 0))
    scal_spec = pl.BlockSpec((1, _LANES), lambda s: (0, 0))

    def as_row(a):      # lane-dense (1, n): the full-length operands
        return a.reshape(1, n).astype(jnp.float32)

    def as_col(a):      # (n, 1): sliced per slab as (block_m, 1) blocks
        return a.reshape(n, 1).astype(jnp.float32)

    D_new, rmin, rarg = pl.pallas_call(
        _make_kernel(method),
        grid=(n // block_m,),
        in_specs=[
            slab_spec,
            col_spec, col_spec, row_spec, row_spec,
            col_spec, row_spec,
            col_spec, row_spec,
            scal_spec, scal_spec,
        ],
        out_specs=[slab_spec, row_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((n, n), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
        ],
        interpret=interpret,
    )(
        D,
        as_row(d_ki), as_row(d_kj), as_col(d_ki), as_col(d_kj),
        as_row(sizes), as_col(sizes),
        as_row(alive), as_col(alive),
        scal, idx,
    )
    return D_new, rmin.reshape(n), rarg.reshape(n)
