"""jit'd public wrapper for fused_gather_aggregate."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.fused_gather_aggregate.kernel import (
    fused_gather_aggregate_pallas, fused_gather_aggregate_v2_pallas)
from repro.kernels.fused_gather_aggregate.ref import (
    fused_gather_aggregate_ref, fused_gather_aggregate_v2_ref)

GATHER_MODES = ("onehot", "dma")


@partial(jax.jit, static_argnames=("num_segments", "agg", "edge_block",
                                   "node_block", "use_pallas", "interpret",
                                   "gather_mode"))
def fused_gather_aggregate(x, src, dst, valid=None, scale=None, *,
                           num_segments: int, agg: str = "sum",
                           edge_block: int = 128, node_block: int = 128,
                           use_pallas: bool = True,
                           interpret: bool | None = None,
                           gather_mode: str = "dma"):
    """Gather source-node rows and aggregate them per destination segment
    in one fused pass — the (E, F) message tensor never reaches HBM.

    x (N, F) — fp32, bf16, or int8; the table streams and stays
    VMEM-resident at its storage width, accumulation is fp32 (int8
    callers fold the per-tensor dequant scale into ``scale``, see
    ``core.aggregations.gather_aggregate(precision=...)``); src/dst (E,)
    int32 endpoint id streams of the packed COO edge buffer, with
    padding marked by -1, any out-of-range id, or ``valid == False``;
    scale: optional (E,) per-edge message scale (the GCN symmetric
    norm). Returns (num_segments, F) float32.

    gather_mode selects the kernel generation: "dma" (default) is the
    one-hot-free v2 kernel — scalar-prefetched id streams, dynamic-slice
    gather, double-buffered scale copies, O(EB * F) per edge block;
    "onehot" is the legacy (N, EB) one-hot MXU contraction kept for
    comparison and DSE featurization (docs/KERNELS.md).

    use_pallas=False falls back to the matching pure-jnp mirror oracle
    (ref.py) — a testing aid whose dense (N, E) / (S, E, F)
    intermediates do not scale to production buffers. The production
    fallback under pjit is
    ``core.aggregations.gather_aggregate(backend="xla")``, which
    materializes the messages and segment-reduces them."""
    if gather_mode not in GATHER_MODES:
        raise ValueError(f"unknown gather_mode {gather_mode!r}; expected "
                         f"one of {GATHER_MODES}")
    src = src.astype(jnp.int32)
    if valid is not None:
        src = jnp.where(valid, src, -1)
    if use_pallas:
        kern = fused_gather_aggregate_v2_pallas if gather_mode == "dma" \
            else fused_gather_aggregate_pallas
        return kern(x, src, dst, num_segments, scale=scale, agg=agg,
                    edge_block=edge_block, node_block=node_block,
                    interpret=interpret)
    ref = fused_gather_aggregate_v2_ref if gather_mode == "dma" \
        else fused_gather_aggregate_ref
    return ref(x, src, dst, num_segments, scale=scale, agg=agg)
