"""jit'd public wrapper for gnn_aggregate."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.gnn_aggregate.kernel import gnn_aggregate_pallas
from repro.kernels.gnn_aggregate.ref import gnn_aggregate_ref


@partial(jax.jit, static_argnames=("agg", "block_nodes", "use_pallas",
                                   "interpret"))
def gnn_aggregate(x, nbr, *, agg: str = "sum", block_nodes: int = 128,
                  use_pallas: bool = True, interpret: bool | None = None):
    """Aggregate neighbor embeddings. x (N,F); nbr (N,K) int32 -1-padded.

    use_pallas=False falls back to the XLA reference (the path used under
    pjit; Pallas engages on single-device serving and via shard_map)."""
    if use_pallas:
        return gnn_aggregate_pallas(x, nbr, agg=agg,
                                    block_nodes=block_nodes,
                                    interpret=interpret)
    return gnn_aggregate_ref(x, nbr, agg=agg)
