"""jit'd wrapper + parallelism-factor -> tile-size mapping."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.tiled_linear.kernel import tiled_matmul_pallas
from repro.kernels.tiled_linear.ref import tiled_matmul_ref

LANE = 128  # MXU systolic dimension


def blocks_from_parallelism(p_in: int, p_out: int) -> tuple:
    """GNNBuilder parallelism factors -> MXU-aligned tile sizes.

    p_in scales the reduction tile (BLOCK_SIZE_IN), p_out the output tile
    (BLOCK_SIZE_OUT); both clamp to hardware-aligned multiples of 128."""
    block_k = max(LANE, min(p_in, 8) * LANE // 2)
    block_n = max(LANE, min(p_out, 8) * LANE // 2)
    return block_k, block_n


@partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                   "use_pallas", "interpret"))
def tiled_matmul(x, w, *, block_m: int = 128, block_n: int = 128,
                 block_k: int = 128, use_pallas: bool = True,
                 interpret: bool | None = None):
    if use_pallas:
        return tiled_matmul_pallas(x, w, block_m=block_m, block_n=block_n,
                                   block_k=block_k, interpret=interpret)
    return tiled_matmul_ref(x, w)
