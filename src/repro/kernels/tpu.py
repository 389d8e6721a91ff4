"""Mosaic compile settings and layout helpers shared by the Pallas kernels.

``VMEM_LIMIT_BYTES`` is the scoped-VMEM limit every kernel compiles
with (``pltpu.CompilerParams(vmem_limit_bytes=...)``) and, by the same
number, the budget ``core.convs.residency_plan`` plans the resident
working set against — so a stack the planner calls legal is a stack
Mosaic accepts. Mosaic's own default scoped limit is much smaller than
the chip's physical VMEM (128 MiB on v5e); 64 MiB leaves the other half
for the compiler's internal scratch.

A VMEM array's minor dimension is tiled to 128 lanes, so a feature
width that is not a multiple of 128 (the 11 raw qm9 features) cannot be
DMA-sliced in row tiles; ``pad_lanes`` zero-pads such tables to the next
multiple of 128 before a kernel sees them and the callers slice back.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

LANES = 128
VMEM_LIMIT_BYTES = 64 * 2**20


def compiler_params() -> pltpu.CompilerParams:
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def pad_lanes(a):
    """Zero-pad the last axis of ``a`` to a multiple of ``LANES``."""
    f = a.shape[-1]
    pad = round_up(max(f, 1), LANES) - f
    if not pad:
        return a
    return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])


def edge_tile(e: int, edge_block: int, dtype=jnp.float32) -> int:
    """Edge-block size for an ``e``-edge stream of ``dtype`` rows:
    ``min(edge_block, e)`` rounded up to a whole sublane tile (8 rows of
    fp32, 16 of bf16, 32 of int8), so every row-tile DMA starts on a
    tile boundary. The callers pad the stream to a whole number of
    blocks with -1 edges."""
    sublanes = 8 * 4 // jnp.dtype(dtype).itemsize
    return round_up(max(min(edge_block, e), 1), sublanes)
