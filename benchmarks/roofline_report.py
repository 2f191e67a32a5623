"""Roofline rows for the clustering hot kernel
(:func:`repro.kernels.pairwise.row_sq_euclidean`), the one row-build
every matrix-free chain step performs (DESIGN.md §11–12)."""

from __future__ import annotations

import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_ROOT, "src")
if _SRC not in sys.path:         # standalone `python benchmarks/...` use
    sys.path.insert(0, _SRC)


def kernel_rows(n: int = 16384, d: int = 128) -> list[str]:
    """Roofline rows for the clustering row-build kernel, from the
    loop-aware :class:`repro.roofline.hlo_cost.HloCost` model over the
    actually-compiled HLO (EXPERIMENTS §Roofline).

    Two variants of the same arithmetic: the fused jnp pass (clean HLO,
    the analyzable reference) and the Pallas tile kernel in interpreter
    mode (what this CPU container can execute; on the TPU target the
    tile loop moves the identical bytes/flops through VMEM).  Model
    flops = 3·n·d (subtract, square, reduce); model bytes =
    4·(n·d + n + d) — one streaming read of the summary block per chain
    step, which is why the kernel sits on the memory roof: arithmetic
    intensity ≈ 3/4 flop/byte, far under the ridge.
    """
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.pairwise import row_sq_euclidean
    from repro.roofline import hw
    from repro.roofline.hlo_cost import HloCost

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(d,)).astype(np.float32))
    Y = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    model_flops = 3.0 * n * d
    model_bytes = 4.0 * (n * d + n + d)

    out = []
    for tag, kw in (("jnp", dict(use_pallas=False)),
                    ("pallas_interp", dict(use_pallas=True, block_n=512,
                                           interpret=True))):
        f = jax.jit(lambda x, Y, kw=kw: row_sq_euclidean(x, Y, **kw))
        hlo = f.lower(x, Y).compile().as_text()
        cost = HloCost(hlo).total()
        f(x, Y).block_until_ready()
        t0 = time.perf_counter()
        reps = 20
        for _ in range(reps):
            r = f(x, Y)
        r.block_until_ready()
        us = (time.perf_counter() - t0) / reps * 1e6
        compute_s = cost.flops / hw.PEAK_FLOPS_BF16
        memory_s = cost.bytes / hw.HBM_BW
        bound = "memory" if memory_s >= compute_s else "compute"
        ratio = model_flops / cost.flops if cost.flops else float("inf")
        out.append(
            f"roofline_row_sq_euclidean_{tag}_n{n}_d{d},{us:.1f},"
            f"hlo_flops={cost.flops:.3g};model_flops={model_flops:.3g};"
            f"hlo_bytes={cost.bytes:.3g};model_bytes={model_bytes:.3g};"
            f"compute_s={compute_s:.3g};memory_s={memory_s:.3g};"
            f"collective_s=0;bound={bound};"
            f"model_over_hlo_flops={ratio:.3f}")
    return out


def main() -> None:
    print("name,us_per_call,derived")
    for row in kernel_rows():
        print(row)
    return None


if __name__ == "__main__":
    main()
