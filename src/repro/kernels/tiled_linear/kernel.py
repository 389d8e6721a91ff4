"""Pallas TPU kernel: tiled matmul (paper §V-B "Linear Layer").

GNNBuilder parallelizes linear layers with BLOCK_SIZE_IN/BLOCK_SIZE_OUT
partition factors controlling MAC parallelism; the TPU analogue is the
(block_m, block_k, block_n) BlockSpec tiling feeding the 128x128 MXU.
Parallelism factors p_in/p_out map to block_k/block_n multiples of the
hardware lane width (see ops.blocks_from_parallelism).

Grid: (M/bm, N/bn, K/bk) with a VMEM fp32 accumulator; K is the reduction
(sequential) dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.aggregations import resolve_interpret


def _matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def tiled_matmul_pallas(x, w, *, block_m: int = 128, block_n: int = 128,
                        block_k: int = 128, interpret: bool | None = None):
    """x: (M, K) @ w: (K, N) -> (M, N), fp32 accumulation in VMEM."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    pm, pn, pk = (-m) % bm, (-n) % bn, (-k) % bk
    if pm or pk:
        x = jnp.pad(x, ((0, pm), (0, pk)))
    if pk or pn:
        w = jnp.pad(w, ((0, pk), (0, pn)))
    mm, nn, kk = m + pm, n + pn, k + pk
    k_steps = kk // bk
    out = pl.pallas_call(
        functools.partial(_matmul_kernel, k_steps=k_steps),
        grid=(mm // bm, nn // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk_: (i, kk_)),
            pl.BlockSpec((bk, bn), lambda i, j, kk_: (kk_, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk_: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mm, nn), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x, w)
    return out[:m, :n]
