"""Pallas TPU kernel: fused neighbor gather + single-pass aggregation
over the *legacy padded (N, K) neighbor-table layout*.

The paper's message-passing engine (Fig. 3) keeps the node-embedding table
in BRAM and streams each node's neighbor block through phi->partial-agg.
TPU adaptation: MAX_NODES-bounded molecular graphs fit the full embedding
table in VMEM (600 x 256 fp32 = 0.6 MB), so the kernel pins the table and
iterates a *padded neighbor table* (N, K) — the CSR neighbor/offset pair
recast as a dense structure XLA-style static shapes want. Aggregations are
the paper's O(1)-state single-pass forms, including Welford var/std.

Note: the hot path no longer runs through this layout. Packed GraphBatch
inference (DESIGN_BATCHING.md) lowers every conv through
``core.aggregations.segment_aggregate`` over flat COO edge streams, whose
fused Pallas form lives in ``kernels/segment_aggregate`` behind the
``backend="xla"|"pallas"`` switch. This kernel remains for single padded
graphs whose neighbor lists are already densified.

Grid: (node_tiles,). Block shapes:
  x        (N, F)  — full table, VMEM-pinned (BRAM analogue)
  nbr      (BN, K) — this tile's neighbor indices (-1 = padding)
  out      (BN, F)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.aggregations import resolve_interpret

AGGS = ("sum", "mean", "min", "max", "var", "std")


def _agg_kernel(x_ref, nbr_ref, out_ref, *, agg: str, k_max: int):
    x = x_ref[...]                       # (N, F) resident table
    nbr = nbr_ref[...]                   # (BN, K)
    bn, _ = nbr.shape
    f = x.shape[1]

    def body(k, state):
        idx = nbr[:, k]                          # (BN,)
        valid = (idx >= 0)[:, None]              # (BN, 1)
        rows = jnp.take(x, jnp.maximum(idx, 0), axis=0)  # (BN, F)
        vf = valid.astype(jnp.float32)
        if agg in ("sum", "mean"):
            acc, cnt = state
            return acc + rows * vf, cnt + vf
        if agg == "min":
            acc, cnt = state
            return jnp.where(valid, jnp.minimum(acc, rows), acc), cnt + vf
        if agg == "max":
            acc, cnt = state
            return jnp.where(valid, jnp.maximum(acc, rows), acc), cnt + vf
        # Welford single-pass (paper §V-B): O(1) state per node
        mean, m2, cnt = state
        cnt_new = cnt + vf
        safe = jnp.maximum(cnt_new, 1.0)
        delta = rows - mean
        mean_new = mean + jnp.where(valid, delta / safe, 0.0)
        m2_new = m2 + jnp.where(valid, delta * (rows - mean_new), 0.0)
        return mean_new, m2_new, cnt_new

    zeros = jnp.zeros((bn, f), jnp.float32)
    cnt0 = jnp.zeros((bn, 1), jnp.float32)
    if agg in ("sum", "mean"):
        init = (zeros, cnt0)
    elif agg == "min":
        init = (jnp.full((bn, f), jnp.inf, jnp.float32), cnt0)
    elif agg == "max":
        init = (jnp.full((bn, f), -jnp.inf, jnp.float32), cnt0)
    else:
        init = (zeros, zeros, cnt0)

    state = jax.lax.fori_loop(0, k_max, body, init)

    if agg == "sum":
        out = state[0]
    elif agg == "mean":
        out = state[0] / jnp.maximum(state[1], 1.0)
    elif agg in ("min", "max"):
        out = jnp.where(jnp.isfinite(state[0]), state[0], 0.0)
    else:
        var = state[1] / jnp.maximum(state[2], 1.0)
        var = jnp.maximum(var, 1e-12)   # clamp: sqrt'(0) = inf -> NaN grads
        out = jnp.sqrt(var) if agg == "std" else var
    out_ref[...] = out.astype(out_ref.dtype)


def gnn_aggregate_pallas(x, nbr, *, agg: str = "sum", block_nodes: int = 128,
                         interpret: bool | None = None):
    """x: (N, F) fp32 node table; nbr: (N, K) int32 neighbor table
    (-1 padded). Returns (N, F) aggregated neighbor features."""
    assert agg in AGGS, agg
    n, f = x.shape
    k_max = nbr.shape[1]
    bn = min(block_nodes, n)
    pad = (-n) % bn
    if pad:
        nbr = jnp.pad(nbr, ((0, pad), (0, 0)), constant_values=-1)
    grid = ((n + pad) // bn,)
    out = pl.pallas_call(
        functools.partial(_agg_kernel, agg=agg, k_max=k_max),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, f), lambda i: (0, 0)),      # full table
            pl.BlockSpec((bn, k_max), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, f), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n + pad, f), x.dtype),
        interpret=resolve_interpret(interpret),
    )(x, nbr)
    return out[:n]
