"""Pallas TPU kernels: blocked online-softmax attention (forward).

Two modes share the (block_q x block_k) online softmax, with fp32
running max / denominator / accumulator in VMEM scratch:

* ``flash_attention_pallas``: causal attention over one sequence per
  (batch*head). Grid: (batch*heads, Sq/bq, Skv/bk), the KV axis the
  sequential (innermost) dimension.
* ``segment_attention_pallas``: non-causal attention restricted to the
  rows of the same graph of a packed batch (GraphGPS's global block).
  Grid: (query tiles, key steps), the key steps as many as the batch's
  widest tile needs; a scalar-prefetched table sends each query tile
  only to the key blocks that hold its graphs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.aggregations import resolve_interpret
from repro.kernels.tpu import compiler_params

NEG_INF = -1e30
_HIGHEST = jax.lax.Precision.HIGHEST


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  kv_steps: int):
    kv_i = pl.program_id(2)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale        # (bq, d)
    k = k_ref[0].astype(jnp.float32)                # (bk, d)
    v = v_ref[0].astype(jnp.float32)                # (bk, dv)
    s = q @ k.T                                     # (bq, bk)
    if causal:
        qi = pl.program_id(1)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (q.shape[0], k.shape[0]), 0)
        k_pos = kv_i * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (q.shape[0], k.shape[0]), 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
    acc_new = acc_prev * corr + p @ v
    m_ref[...], l_ref[...], acc_ref[...] = m_new, l_new, acc_new

    @pl.when(kv_i == kv_steps - 1)
    def _done():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool | None = None):
    """q: (BH, Sq, D); k, v: (BH, Skv, D) -> (BH, Sq, D)."""
    bh, sq, d = q.shape
    skv, dv = k.shape[1], v.shape[-1]
    bq, bk = min(block_q, sq), min(block_k, skv)
    assert sq % bq == 0 and skv % bk == 0, "pad seq to tile multiples"
    kv_steps = skv // bk
    scale = d ** -0.5
    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, kv_steps=kv_steps),
        grid=(bh, sq // bq, kv_steps),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)


def _segment_kernel(first_ref, count_ref, q_ref, k_ref, v_ref, gq_ref,
                    gk_ref, o_ref, m_ref, l_ref, acc_ref, *, scale: float,
                    num_heads: int, num_graphs: int):
    """One (query tile, key step) of segment-masked attention. The
    heads stay side by side in the (tile, H*dh) blocks: head ``h`` reads
    and writes only its own columns, by a column mask, so no block is
    sliced at a lane offset, and the MXU does the same passes as it
    would for a dh-wide contraction."""
    i, j = pl.program_id(0), pl.program_id(1)
    d = q_ref.shape[-1]
    head_of = jax.lax.broadcasted_iota(jnp.int32, (1, d), 1) \
        // (d // num_heads)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < count_ref[i])
    def _step():
        q = q_ref[...].astype(jnp.float32) * scale          # (bq, d)
        k = k_ref[...].astype(jnp.float32)                  # (bk, d)
        v = v_ref[...].astype(jnp.float32)
        gq = gq_ref[...]                                    # (bq, 1)
        same = (gq == gk_ref[...]) & (gq < num_graphs)      # (bq, bk)
        acc = acc_ref[...]
        for h in range(num_heads):
            mine = head_of == h                             # (1, d)
            s = jax.lax.dot_general(
                jnp.where(mine, q, 0.0), k, (((1,), (1,)), ((), ())),
                precision=_HIGHEST, preferred_element_type=jnp.float32)
            s = jnp.where(same, s, NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.where(same, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + p.sum(axis=-1, keepdims=True)
            m_ref[h] = m_new
            pv = jnp.dot(p, v, precision=_HIGHEST,
                         preferred_element_type=jnp.float32)
            acc = jnp.where(mine, acc * corr + pv, acc)
        acc_ref[...] = acc

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        acc = acc_ref[...]
        out = jnp.zeros_like(acc)
        for h in range(num_heads):
            out = jnp.where(head_of == h,
                            acc / jnp.maximum(l_ref[h], 1e-30), out)
        o_ref[...] = out.astype(o_ref.dtype)


def segment_attention_pallas(q, k, v, graph_id, kv_first, kv_count, *,
                             num_heads: int, num_graphs: int,
                             block: int = 128,
                             interpret: bool | None = None):
    """Multi-head attention of each row over the rows of its own graph.

    q, k, v: (N, H*dh), N a multiple of ``block``; graph_id (N,) int32,
    ``num_graphs`` on padding rows, whose output is 0. ``kv_first`` and
    ``kv_count`` (N/block,) int32, scalar-prefetched: query tile ``i``
    visits the key blocks ``kv_first[i] .. kv_first[i] + kv_count[i] -
    1``, those that hold its graphs (``ops.segment_tiles``). The grid
    has ``max(kv_count)`` key steps, a bound read on the device, so a
    graph of any size that fits the batch is attended whole; a step
    past ``kv_count[i]`` computes nothing and fetches nothing new.
    Within a step the mask is ``graph_id`` equality. fp32 online
    softmax, scale ``dh ** -0.5``."""
    n, d = q.shape
    assert n % block == 0 and d % num_heads == 0
    tiles = n // block
    kv_count = kv_count.astype(jnp.int32)
    kv_steps = jnp.maximum(jnp.max(kv_count), 1)

    def kv_block(i, j, first, count):
        return (first[i] + jnp.minimum(j, jnp.maximum(count[i] - 1, 0)), 0)

    def gk_block(i, j, first, count):
        return (0, kv_block(i, j, first, count)[0])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(tiles, kv_steps),
        in_specs=[
            pl.BlockSpec((block, d), lambda i, j, f, c: (i, 0)),
            pl.BlockSpec((block, d), kv_block),
            pl.BlockSpec((block, d), kv_block),
            pl.BlockSpec((block, 1), lambda i, j, f, c: (i, 0)),
            pl.BlockSpec((1, block), gk_block),
        ],
        out_specs=pl.BlockSpec((block, d), lambda i, j, f, c: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((num_heads, block, 1), jnp.float32),
            pltpu.VMEM((num_heads, block, 1), jnp.float32),
            pltpu.VMEM((block, d), jnp.float32),
        ])
    gid = graph_id.astype(jnp.int32)
    return pl.pallas_call(
        functools.partial(_segment_kernel, scale=(d // num_heads) ** -0.5,
                          num_heads=num_heads, num_graphs=num_graphs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        compiler_params=compiler_params(),
        interpret=resolve_interpret(interpret),
        name="segment_attention",
    )(kv_first.astype(jnp.int32), kv_count, q, k, v, gid[:, None],
      gid[None, :])
