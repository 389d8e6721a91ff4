"""jit'd public wrapper for segment_softmax."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.segment_softmax.kernel import segment_softmax_pallas
from repro.kernels.segment_softmax.ref import segment_softmax_ref


@partial(jax.jit, static_argnames=("num_segments", "edge_block",
                                   "use_pallas", "interpret"))
def segment_softmax(logits, seg_ids, valid=None, *, num_segments: int,
                    edge_block: int = 128, use_pallas: bool = True,
                    interpret: bool | None = None):
    """Normalize packed per-edge logits within each destination segment.

    logits (E,) float — any magnitude; the online-softmax state machine
    subtracts the running per-segment max before every exp, so +-1e4
    logits stay finite. seg_ids (E,) int32 destination ids, with padding
    marked by -1, any id >= num_segments, or ``valid == False``. A -inf
    logit on a valid edge is a masked attention slot. Returns (E,)
    float32 weights: each non-empty segment's rows sum to 1; padding
    edges, masked slots, and members of all-masked segments get exactly
    0 — never NaN/Inf.

    use_pallas=False falls back to the dense one-hot oracle (ref.py) —
    a testing aid with an O(num_segments * E) intermediate. The
    production fallback under pjit is
    ``core.aggregations.segment_softmax(backend="xla")``."""
    seg_ids = seg_ids.astype(jnp.int32)
    if valid is not None:
        seg_ids = jnp.where(valid, seg_ids, -1)
    if use_pallas:
        return segment_softmax_pallas(logits, seg_ids, num_segments,
                                      edge_block=edge_block,
                                      interpret=interpret)
    return segment_softmax_ref(logits, seg_ids, num_segments)
