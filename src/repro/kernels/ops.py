"""Jit'd public wrappers around the Pallas kernels.

Handles padding/alignment (TPU lane multiples), compiles the kernels with
Mosaic on a TPU and selects interpret mode on any other backend, and
provides ``lance_williams_kernelized`` —
the unified merge loop (:mod:`repro.core.engine`) composed with the
Pallas min-scan argmin op and the Pallas ``lw_update`` update op.  The
batched variant is the same composition under ``vmap``: the
``pallas_call`` batching rule prepends the batch as a leading grid
dimension, i.e. the ``grid=(B, slabs)`` schedule, with no dedicated
batch kernels.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.engine import (
    KERNEL_STAGE_ALIGN,
    VARIANTS,
    LWResult,
    resolve_compaction,
    resolve_n_steps,
    run_kernel,
    symmetrize,
)
from repro.core.linkage import METHODS
from repro.kernels.lw_update import lw_update_pallas
from repro.kernels.minscan import masked_argmin_pallas
from repro.kernels.pairwise import pairwise_sq_euclidean_pallas


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x: jax.Array, mult: int, axis: int, value: float = 0.0) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@partial(jax.jit, static_argnames=("block_m", "block_n"))
def pairwise(X: jax.Array, Y: jax.Array | None = None, *, block_m: int = 256,
             block_n: int = 256) -> jax.Array:
    """Padded/tiled pairwise squared-Euclidean distances via the kernel."""
    X = jnp.asarray(X, jnp.float32)
    Y = X if Y is None else jnp.asarray(Y, jnp.float32)
    n, m = X.shape[0], Y.shape[0]
    bm, bn = min(block_m, max(8, n)), min(block_n, max(128, m))
    Xp = _pad_to(_pad_to(X, 128, axis=1), bm, axis=0)
    Yp = _pad_to(_pad_to(Y, 128, axis=1), bn, axis=0)
    D = pairwise_sq_euclidean_pallas(
        Xp, Yp, block_m=bm, block_n=bn, interpret=_interpret()
    )
    return D[:n, :m]


@partial(jax.jit, static_argnames=("block_m",))
def masked_argmin(D: jax.Array, alive: jax.Array, *, block_m: int = 256):
    """Masked (min, flat-argmin) of a square matrix via the kernel.

    The flat index refers to the *padded* row length; the wrapper converts
    back to (r, c) of the original matrix.
    """
    n = D.shape[0]
    npad = n + ((-n) % 128)                     # square, lane-aligned
    Dp = _pad_to(_pad_to(jnp.asarray(D, jnp.float32), npad, axis=0), npad, axis=1)
    mp = npad
    bm = block_m if npad % block_m == 0 else 128
    alive_p = _pad_to(jnp.asarray(alive).astype(jnp.float32), npad, axis=0)
    v, flat = masked_argmin_pallas(Dp, alive_p, block_m=bm, interpret=_interpret())
    r, c = flat // mp, flat % mp
    return v, r * n + c


def lw_update(method: str, d_ki, d_kj, d_ij, n_i, n_j, sizes, keep, *,
              block_n: int = 2048):
    """Padded fused LW row update via the kernel."""
    n = d_ki.shape[0]
    def pad(a):
        return _pad_to(jnp.asarray(a, jnp.float32), 128, axis=0)

    bn = min(block_n, pad(d_ki).shape[0])
    out = lw_update_pallas(
        method,
        pad(d_ki), pad(d_kj), d_ij, n_i, n_j,
        pad(sizes), pad(keep.astype(jnp.float32)),
        block_n=bn, interpret=_interpret(),
    )
    return out[:n]


# ---------------------------------------------------------------------------
# the kernelized engine compositions
# ---------------------------------------------------------------------------


def _check(method: str, variant: str) -> None:
    if method not in METHODS:
        raise ValueError(f"unknown linkage method {method!r}")
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; pick from {VARIANTS}")


def resolve_kernel_compaction(flag, n: int, n_steps: int) -> bool:
    """Kernel-path compaction switch: the plan runs on the lane-padded
    size and stages stay multiples of :data:`KERNEL_STAGE_ALIGN`."""
    npad = n + ((-n) % KERNEL_STAGE_ALIGN)
    return resolve_compaction(
        flag, npad, n_steps,
        min_stage=KERNEL_STAGE_ALIGN, align=KERNEL_STAGE_ALIGN,
    )


@partial(
    jax.jit,
    static_argnames=(
        "method", "variant", "stop_at_k", "with_threshold", "block_m",
        "compaction",
    ),
)
def _kernelized_run(D, threshold, *, method, variant, stop_at_k,
                    with_threshold, block_m, compaction=False):
    D = symmetrize(D)
    n = D.shape[0]

    # pad once so every kernel call inside the loop is lane-aligned
    npad = n + ((-n) % 128)
    Dp = jnp.zeros((npad, npad), jnp.float32).at[:n, :n].set(D)
    return run_kernel(
        Dp,
        jnp.arange(npad) < n,
        method=method,
        n_steps=resolve_n_steps(n, stop_at_k),
        variant=variant,
        distance_threshold=threshold if with_threshold else None,
        block_m=block_m,
        interpret=_interpret(),
        compaction=compaction,
    )


def lance_williams_kernelized(
    D: jax.Array,
    method: str = "complete",
    *,
    variant: str = "baseline",
    stop_at_k: int = 1,
    distance_threshold: float | None = None,
    block_m: int = 256,
    compaction: bool | str = "auto",
) -> LWResult:
    """Serial LW with Pallas inner loops (the fused one-pass ``lw_step``
    kernel for ``baseline``/``rowmin``; min-scan + ``lw_update`` for the
    ``lazy`` drain).

    Merge indices are bit-compatible with
    :func:`repro.core.lance_williams.lance_williams` (same masking, same
    row-major tie-breaking) with float-tolerance distances — validated in
    tests.  ``variant``/``stop_at_k``/``distance_threshold``/``compaction``
    behave as on every other backend (engine-level features; the
    threshold value is a traced operand, so it never triggers a
    recompile; compaction stages stay lane-aligned).
    """
    _check(method, variant)
    n = int(D.shape[0])
    return _kernelized_run(
        D,
        jnp.float32(0.0 if distance_threshold is None else distance_threshold),
        method=method,
        variant=variant,
        stop_at_k=stop_at_k,
        with_threshold=distance_threshold is not None,
        block_m=block_m,
        compaction=resolve_kernel_compaction(
            compaction, n, resolve_n_steps(n, stop_at_k)
        ),
    )


@partial(
    jax.jit,
    static_argnames=(
        "method", "n_steps", "variant", "with_threshold", "block_m",
        "compaction",
    ),
)
def _kernelized_batch_run(Db, n_real, threshold, *, method, n_steps, variant,
                          with_threshold, block_m, compaction=False):
    Db = symmetrize(Db)
    B, n_pad = Db.shape[0], Db.shape[1]

    # pad once so every kernel call inside the loop is lane-aligned
    npad = n_pad + ((-n_pad) % 128)
    Dp = jnp.zeros((B, npad, npad), jnp.float32).at[:, :n_pad, :n_pad].set(Db)
    alive0 = jnp.arange(npad)[None, :] < n_real[:, None]

    def run(D, alive):
        return run_kernel(
            D,
            alive,
            method=method,
            n_steps=n_steps,
            variant=variant,
            distance_threshold=threshold if with_threshold else None,
            block_m=block_m,
            interpret=_interpret(),
            compaction=compaction,
        )

    return jax.vmap(run)(Dp, alive0)


def lance_williams_kernelized_batch(
    Db: jax.Array,
    n_real: jax.Array,
    *,
    method: str = "complete",
    n_steps: int,
    variant: str = "baseline",
    distance_threshold: float | None = None,
    block_m: int = 256,
    compaction: bool | str = "auto",
) -> LWResult:
    """Batched serial LW with Pallas inner loops — ``vmap`` of the
    single-problem composition.

    ``Db`` is ``(B, n_pad, n_pad)`` stacked problems (slots
    ``>= n_real[b]`` dead from birth).  The ``pallas_call`` batching rule
    turns each kernel invocation into one launch with a leading batch
    grid dimension.  Returns batched ``LWResult``: ``(B, n_steps, 4)``
    merges (rows past problem ``b``'s real merges are garbage — the
    scheduler slices them off) and ``(B,)`` merge counts.

    ``compaction`` resolves on the lane-padded batch shape (stages stay
    128-multiples); the bucket scheduler passes its signature's already
    resolved flag, direct callers get the same ``"auto"`` policy as
    every other entry point.
    """
    _check(method, variant)
    return _kernelized_batch_run(
        Db,
        n_real,
        jnp.float32(0.0 if distance_threshold is None else distance_threshold),
        method=method,
        n_steps=n_steps,
        variant=variant,
        with_threshold=distance_threshold is not None,
        block_m=block_m,
        compaction=resolve_kernel_compaction(
            compaction, int(Db.shape[-1]), n_steps
        ),
    )
