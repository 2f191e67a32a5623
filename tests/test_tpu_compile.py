"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Nothing runs: each test lowers one kernel at a real width for one chip of
a described ``v5e:2x2`` topology and asserts that the chip's compiler
accepted it as a Mosaic kernel (``tpu_custom_call`` in the compiled
text).  That catches what interpret mode cannot — block shapes that break
the (8, 128) tiling rule, shape casts Mosaic cannot lay out, and more
fast memory than a kernel may use — without a chip.

The topology is described inside a module fixture, never at import time,
so every xdist worker collects the same tests and only the worker that
runs this file loads the TPU compiler.  Keep every such compile in this
one file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.lw_step import lw_step_pallas
from repro.kernels.lw_update import lw_update_pallas
from repro.kernels.minscan import masked_argmin_pallas
from repro.kernels.pairwise import (
    pairwise_sq_euclidean_pallas,
    row_sq_euclidean_pallas,
)

N = 4096
D = 128
ROW_M = 100_352          # 10^5 points padded to the row kernel's 512 block


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_pairwise_compiles_for_v5e(one_chip):
    x = _spec(one_chip, (N, D))
    text = _compiled_text(
        lambda X: pairwise_sq_euclidean_pallas(X, X, interpret=False), x
    )
    assert "tpu_custom_call" in text


def test_row_kernel_compiles_for_v5e(one_chip):
    text = _compiled_text(
        lambda w, Y: row_sq_euclidean_pallas(w, Y, interpret=False),
        _spec(one_chip, (D,)),
        _spec(one_chip, (ROW_M, D)),
    )
    assert "tpu_custom_call" in text


def test_minscan_compiles_for_v5e(one_chip):
    text = _compiled_text(
        lambda Dm, alive: masked_argmin_pallas(Dm, alive, interpret=False),
        _spec(one_chip, (N, N)),
        _spec(one_chip, (N,)),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("method", ["complete", "ward"])
def test_lw_step_compiles_for_v5e(one_chip, method):
    vec = _spec(one_chip, (N,))
    scalar = _spec(one_chip, ())
    index = _spec(one_chip, (), jnp.int32)

    def step(Dm, d_ki, d_kj, d_ij, n_i, n_j, sizes, alive, i, j):
        return lw_step_pallas(method, Dm, d_ki, d_kj, d_ij, n_i, n_j,
                              sizes, alive, i, j, interpret=False)

    text = _compiled_text(
        step, _spec(one_chip, (N, N)), vec, vec, scalar, scalar, scalar,
        vec, vec, index, index,
    )
    assert "tpu_custom_call" in text


def test_rmsd_program_compiles_for_v5e(one_chip):
    """The paper's distributed RMSD build (a ``shard_map`` program) at its
    n = 1968 lowers for a TPU: its Kabsch step must not bring a library
    SVD, whose TPU lowering is a loop that ``shard_map`` cannot type."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.distributed import make_cluster_mesh, pairwise_program
    from repro.core.engine import AXIS

    mesh = make_cluster_mesh(one_chip.device_set)
    confs = jax.ShapeDtypeStruct(
        (1968, 24, 3), jnp.float32,
        sharding=NamedSharding(mesh, P(AXIS, None, None)),
    )
    pairwise_program("rmsd", mesh, 3).lower(confs).compile()


def test_lw_update_compiles_for_v5e(one_chip):
    vec = _spec(one_chip, (N,))
    scalar = _spec(one_chip, ())

    def update(d_ki, d_kj, d_ij, n_i, n_j, sizes, keep):
        return lw_update_pallas("ward", d_ki, d_kj, d_ij, n_i, n_j, sizes,
                                keep, interpret=False)

    text = _compiled_text(update, vec, vec, scalar, scalar, scalar, vec, vec)
    assert "tpu_custom_call" in text


def test_staged_points_chain_compiles_for_v5e(one_chip):
    """The corpus cell's program: the matrix-free ward chain on 32768 ×
    128 summaries, staged at 32768, 16384, 8192 and 4096 rows.  No
    (n, n) buffer.  Temporaries hold the loop's copy of the summaries,
    the half-size stages' copies (later stages reuse the first half's
    room), the carried merge list, which the chip lays out 128 lanes
    wide (16.8 MB for 32767 rows, as in the one-stage program), and
    what the compiler keeps in fast memory, under half the summaries."""
    from repro.core.nnchain import _run_points, points_stage_plan

    n = 32_768
    assert len(points_stage_plan(n, D, n - 1)) == 4
    compiled = _run_points.lower(
        _spec(one_chip, (n, D)), _spec(one_chip, (n,), jnp.bool_),
        method="ward", n_steps=n - 1, use_pallas=False, block_n=512,
        interpret=False,
    ).compile()
    assert f"[{n},{n}]" not in compiled.as_text()
    summaries = n * D * 4
    merge_list = (n - 1 + 7) // 8 * 8 * 128 * 4
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 2 * summaries + summaries // 2 + merge_list, temp
