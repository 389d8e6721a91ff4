"""jit'd public wrapper for segment_aggregate."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.segment_aggregate.kernel import (
    segment_aggregate_pallas, segment_aggregate_v2_pallas)
from repro.kernels.segment_aggregate.ref import segment_aggregate_ref

GATHER_MODES = ("onehot", "dma")


@partial(jax.jit, static_argnames=("num_segments", "agg", "edge_block",
                                   "node_block", "use_pallas", "interpret",
                                   "gather_mode"))
def segment_aggregate(messages, seg_ids, valid=None, *, num_segments: int,
                      agg: str = "sum", edge_block: int = 128,
                      node_block: int = 128, use_pallas: bool = True,
                      interpret: bool | None = None,
                      gather_mode: str = "dma"):
    """Aggregate packed COO edge messages per destination segment.

    messages (E, F) — fp32, bf16, or int8; tiles stream at the storage
    width, accumulation is fp32 (callers dequantize int8 outputs, see
    ``core.aggregations.segment_aggregate(precision=...)``); seg_ids
    (E,) int32 destination ids, with padding marked by -1, any id >=
    num_segments (the packed-batch overflow bucket), or
    ``valid == False``. Returns (num_segments, F) float32.

    gather_mode selects the kernel generation: "dma" (default) is the
    one-hot-free v2 schedule — scalar-prefetched dst stream,
    double-buffered message-tile DMA, whole-table VMEM accumulators
    (incl. the Welford mean/M2 pair), one sweep over the edge stream;
    "onehot" is the legacy (NB, EB) destination one-hot schedule kept
    for comparison and DSE featurization (docs/KERNELS.md).

    use_pallas=False falls back to the pure-jnp mirror oracle (ref.py) —
    a testing aid whose dense (N, E, F) min/max/var intermediates do not
    scale to production buffers. The production fallback under pjit is
    ``core.aggregations.segment_aggregate(backend="xla")``, which is also
    the process default; Pallas engages on single-device serving."""
    if gather_mode not in GATHER_MODES:
        raise ValueError(f"unknown gather_mode {gather_mode!r}; expected "
                         f"one of {GATHER_MODES}")
    seg_ids = seg_ids.astype(jnp.int32)
    if valid is not None:
        seg_ids = jnp.where(valid, seg_ids, -1)
    if use_pallas:
        kern = segment_aggregate_v2_pallas if gather_mode == "dma" \
            else segment_aggregate_pallas
        return kern(messages, seg_ids, num_segments, agg=agg,
                    edge_block=edge_block, node_block=node_block,
                    interpret=interpret)
    return segment_aggregate_ref(messages, seg_ids, num_segments, agg=agg)
