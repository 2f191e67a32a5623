"""Pallas TPU kernel: masked min + argmin scan over the distance matrix.

This is the paper's step 1 — every iteration scans the live cells of the
(row-sharded) distance matrix for the minimum.  The kernel tiles the matrix
into ``(bm, n)`` row slabs, applies the liveness/diagonal mask in VMEM, and
emits each row's ``(min, first-col argmin)``; a tiny jnp epilogue picks
the first row attaining the global minimum.  Tie-breaking is row-major
first-minimum, bit-identical to the serial engine.

Row vectors travel as ``(bm, 1)`` column blocks, so the row mask and the
per-row outputs need no lane-to-sublane relayout, and every reduction is
a lane-axis ``min`` that Mosaic lowers directly.  Batched execution needs
no dedicated kernel: under ``jax.vmap`` the ``pallas_call`` batching rule
prepends the batch as a leading grid dimension (``grid=(B, slabs)``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


#: Bytes of one f32 ``(block_m, n)`` slab buffer.  The slab kernels
#: double-buffer their slab input (and ``lw_step`` its slab output too),
#: so four such buffers (8 MiB) plus the ``(block_m, 1)`` row blocks stay
#: inside a v5e's 16 MiB default scoped-VMEM limit.
SLAB_BYTES = 2 * 2**20


def slab_rows(n: int, block_m: int) -> int:
    """Slab height for an ``n``-column matrix: ``block_m`` halved until
    the slab fits :data:`SLAB_BYTES` (never below the 8-row sublane
    tile).  Rows shrink as ``n`` grows, so one kernel serves every
    lane-aligned size without raising the VMEM limit."""
    bm = block_m
    while bm > 8 and (4 * bm * n > SLAB_BYTES or n % bm):
        bm //= 2
    assert n % bm == 0, (n, block_m, bm)
    return bm


def row_min_args(dm: jax.Array, col_g: jax.Array, n: int):
    """Per-row ``(min, first-col argmin)`` of a masked ``(bm, n)`` tile, as
    two ``(bm, 1)`` columns (the layout the kernels store)."""
    rmin = jnp.min(dm, axis=1, keepdims=True)
    rarg = jnp.min(jnp.where(dm == rmin, col_g, n), axis=1, keepdims=True)
    return rmin, rarg


def _minscan_kernel(d_ref, alive_row_ref, alive_col_ref, rmin_ref, rarg_ref):
    i = pl.program_id(0)
    d = d_ref[...]                              # (bm, n) float32
    bm, n = d.shape
    row_live = alive_row_ref[...] != 0          # (bm, 1)
    col_live = alive_col_ref[...] != 0          # (1, n)

    row_g = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, n), 0)
    col_g = jax.lax.broadcasted_iota(jnp.int32, (bm, n), 1)
    valid = row_live & col_live & (row_g != col_g)
    rmin, rarg = row_min_args(jnp.where(valid, d, jnp.inf), col_g, n)
    rmin_ref[...] = rmin
    rarg_ref[...] = rarg


def masked_argmin_pallas(
    D: jax.Array,
    alive: jax.Array,
    *,
    block_m: int = 256,
    interpret: bool = False,
):
    """Masked (min, flat-argmin) of a square matrix.

    ``alive`` is an ``(n,)`` liveness vector (float/bool); dead rows, dead
    columns and the diagonal are excluded.  Returns scalar ``(min, flat)``.
    Requires ``n % block_m == 0`` (see the ops wrapper for padding);
    the slab height then shrinks to fit VMEM (:func:`slab_rows`).
    """
    n = D.shape[0]
    assert D.shape == (n, n) and n % block_m == 0, (D.shape, block_m)
    block_m = slab_rows(n, block_m)
    alive_f = alive.astype(jnp.float32)

    rmin, rarg = pl.pallas_call(
        _minscan_kernel,
        grid=(n // block_m,),
        in_specs=[
            pl.BlockSpec((block_m, n), lambda i: (i, 0)),
            pl.BlockSpec((block_m, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_m, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
        ],
        interpret=interpret,
    )(D, alive_f.reshape(n, 1), alive_f.reshape(1, n))

    rmin, rarg = rmin[:, 0], rarg[:, 0]
    v = jnp.min(rmin)
    r = jnp.min(jnp.where(rmin == v, jnp.arange(n), n))   # first row wins ties
    r = jnp.minimum(r, n - 1)
    return v, r * n + rarg[r]
