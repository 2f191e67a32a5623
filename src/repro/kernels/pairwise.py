"""Pallas TPU kernel: tiled pairwise squared-Euclidean distance matrix.

The paper's pre-clustering phase builds the full ``(n, n)`` distance matrix
(its parallelized-RMSD step).  In Gram form
``D = ‖x‖² + ‖y‖² − 2·X Yᵀ`` the build is one big matmul — this kernel
tiles it so each grid cell streams an ``(bm, d)`` and a ``(bn, d)`` slab of
points from HBM into VMEM, runs the ``(bm, d) × (d, bn)`` contraction on
the MXU, and fuses the norm/add/clamp epilogue in registers — the distance
tile never round-trips to HBM in fp32 intermediates.

Block shapes default to (256, 256) tiles with the feature dim ``d`` kept
whole (padded to a lane multiple by the wrapper): VMEM footprint
``2·b·d + b²`` floats ≈ 0.8 MB for b=256, d=256 — far under the ~16 MB
v5e VMEM budget, leaving room for double buffering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pairwise_kernel(x_ref, y_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)          # (bm, d)
    y = y_ref[...].astype(jnp.float32)          # (bn, d)
    xx = jnp.sum(x * x, axis=1)                 # (bm,)
    yy = jnp.sum(y * y, axis=1)                 # (bn,)
    g = jax.lax.dot_general(
        x, y,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,    # float32 passes, not bf16
        preferred_element_type=jnp.float32,
    )                                           # (bm, bn) on the MXU
    d = xx[:, None] + yy[None, :] - 2.0 * g
    out_ref[...] = jnp.maximum(d, 0.0)


def pairwise_sq_euclidean_pallas(
    X: jax.Array,
    Y: jax.Array | None = None,
    *,
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """``(n, d) × (m, d) → (n, m)`` squared distances, tiled for VMEM.

    Inputs must already be padded so ``n % block_m == m % block_n == 0``
    and ``d`` is a multiple of 128 (use :func:`repro.kernels.ops.pairwise`
    for the padding wrapper).
    """
    Y = X if Y is None else Y
    n, d = X.shape
    m = Y.shape[0]
    assert n % block_m == 0 and m % block_n == 0, (n, m, block_m, block_n)

    grid = (n // block_m, m // block_n)
    return pl.pallas_call(
        _pairwise_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), jnp.float32),
        interpret=interpret,
    )(X, Y)


pairwise_sq_euclidean_pallas_jit = functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "interpret")
)(pairwise_sq_euclidean_pallas)


def row_sq_euclidean(
    x: jax.Array,
    Y: jax.Array,
    *,
    use_pallas: bool = False,
    block_n: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """``(d,) × (m, d) → (m,)`` squared distances — the ONE row-build
    dispatch every matrix-free chain composition calls.

    The serial points mode (:mod:`repro.core.nnchain`) and the sharded
    points mode (:mod:`repro.core.distributed`, each shard passing its
    local ``(m/p, d)`` block) both route here: one fused jnp pass by
    default, or tile-by-tile through :func:`row_sq_euclidean_pallas`
    (``use_pallas``; inputs must then satisfy the kernel's padding
    contract).  Keeping the arithmetic in one place keeps the serial and
    sharded engines' distances bit-identical — the equivalence tests
    rely on it.

    Eager calls record ``m`` evaluations on any open
    :class:`~repro.core.distance.DistanceBudget`; a call under tracing
    is accounted by its engine's measured trip count instead
    (``ChainResult.iters`` — see the distance module docstring).
    """
    from repro.core.distance import _concrete, record_queries

    if _concrete(x, Y):
        record_queries(Y.shape[0], "row")
    if use_pallas:
        return row_sq_euclidean_pallas(
            x, Y, block_n=block_n, interpret=interpret
        )
    diff = Y - x[None, :]
    return jnp.sum(diff * diff, axis=-1)


def _row_kernel(x_ref, y_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)          # (1, d) — the chain tip
    y = y_ref[...].astype(jnp.float32)          # (bn, d) — a points tile
    xx = jnp.sum(x * x)
    yy = jnp.sum(y * y, axis=1)                 # (bn,)
    g = jax.lax.dot_general(
        x, y,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,    # float32 passes, not bf16
        preferred_element_type=jnp.float32,
    )                                           # (1, bn) on the MXU
    out_ref[...] = jnp.maximum(xx + yy[None, :] - 2.0 * g, 0.0)


def row_sq_euclidean_pallas(
    x: jax.Array,
    Y: jax.Array,
    *,
    block_n: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """``(d,) × (m, d) → (m,)`` squared distances — ONE row, tile-by-tile.

    The matrix-free NN-chain points mode (DESIGN.md §11) calls this once
    per chain extension: the candidate row against the whole summary
    array streams through VMEM in ``(block_n, d)`` tiles and the full
    ``(m, m)`` matrix is never formed anywhere.  Inputs must already be
    padded (``m % block_n == 0``, ``d`` a multiple of 128 — the
    ``nn_chain_from_points`` wrapper pads once, up front).
    """
    m, d = Y.shape
    assert x.shape == (d,) and m % block_n == 0 and d % 128 == 0, (
        x.shape, Y.shape, block_n,
    )
    from repro.core.engine import match_vma

    # inside shard_map the kernel's operands and output must all vary
    # over the same manual axes (the tip is replicated, the block is not)
    x, Y = match_vma((x, Y))
    vma = jax.typeof(Y).vma
    if interpret and vma:
        # the generic interpreter re-evaluates the kernel with replicated
        # literals, which shard_map's vma check rejects; the TPU
        # interpreter types them correctly
        interpret = pltpu.InterpretParams()
    out = pl.pallas_call(
        _row_kernel,
        grid=(m // block_n,),
        in_specs=[
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, m), jnp.float32, vma=vma),
        interpret=interpret,
    )(x[None, :], Y)
    return out[0]
