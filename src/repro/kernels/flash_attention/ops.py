"""Wrappers of the attention kernels: ``flash_attention`` (causal, one
sequence per head) and ``segment_attention`` (per graph of a packed
batch), with the key-range table the latter's kernel is given and the
host counters of the work it does."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention.kernel import (NEG_INF,
                                                  flash_attention_pallas,
                                                  segment_attention_pallas)
from repro.kernels.flash_attention.ref import attention_ref
from repro.runtime import trace

#: rows of a query tile (and of a key block) of the segment attention
SEGMENT_BLOCK = 128


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                   "use_pallas", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, use_pallas: bool = True,
                    interpret: bool | None = None):
    """q/k/v: (B, H, S, D) or (BH, S, D)."""
    squeeze = q.ndim == 4
    if squeeze:
        b, h, s, d = q.shape
        rs = lambda t: t.reshape(b * h, *t.shape[2:])
        q, k, v = rs(q), rs(k), rs(v)
    if use_pallas:
        # pad seq dims to tile multiples
        sq, skv = q.shape[1], k.shape[1]
        bq, bk = min(block_q, sq), min(block_k, skv)
        pq, pk = (-sq) % bq, (-skv) % bk
        if pq:
            q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
        if pk:
            k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
        out = flash_attention_pallas(q, k, v, causal=causal, block_q=bq,
                                     block_k=bk, interpret=interpret)
        out = out[:, :sq]
    else:
        out = attention_ref(q, k, v, causal=causal)
    if squeeze:
        out = out.reshape(b, h, s, -1)
    return out


def segment_tiles(graph_num_nodes, num_rows: int, block: int, xp=np):
    """The key range of each query tile of a packed batch, from the
    graph sizes alone (graphs lie contiguously from row 0, in order;
    the rest is padding): ``(first, count)``, (num_rows // block,)
    int32, the first key block and the number of key blocks that hold
    the graphs of the tile's rows; 0 blocks for a tile of padding.
    ``xp`` is ``numpy`` (host counters) or ``jax.numpy`` (the program),
    so both read the same table."""
    sizes = xp.asarray(graph_num_nodes).astype(xp.int32)
    ends = xp.cumsum(sizes)
    starts = ends - sizes
    total = ends[-1]
    last = sizes.shape[0] - 1
    row0 = xp.arange(num_rows // block, dtype=xp.int32) * block
    row1 = xp.minimum(row0 + block, total) - 1
    g0 = xp.minimum(xp.searchsorted(ends, row0, side="right"), last)
    g1 = xp.minimum(xp.searchsorted(ends, row1, side="right"), last)
    live = row0 < total
    first = starts[g0] // block
    count = (ends[g1] - 1) // block - first + 1
    return (xp.where(live, first, 0).astype(xp.int32),
            xp.where(live, count, 0).astype(xp.int32))


def _segment_attention_xla(q, k, v, graph_id, first, count, *,
                           num_heads: int, num_graphs: int, block: int):
    """The kernel's computation in plain XLA: each query tile visits
    its key blocks in the same order, ``max(count)`` steps of the same
    masked online softmax."""
    n, d = q.shape
    dh = d // num_heads
    tiles = n // block
    blocks = lambda a: a.astype(jnp.float32).reshape(  # noqa: E731
        tiles, block, num_heads, dh)
    qh = blocks(q) * dh ** -0.5
    kb, vb = blocks(k), blocks(v)
    gb = graph_id.reshape(tiles, block)
    real = (gb < num_graphs)[:, None, :, None]               # (T,1,q,1)
    hi = jax.lax.Precision.HIGHEST

    def step(j, carry):
        m, l, acc = carry                                     # (T,H,q,·)
        idx = first + jnp.minimum(j, jnp.maximum(count - 1, 0))
        same = (gb[:, None, :, None] == gb[idx][:, None, None, :]) \
            & real & (j < count)[:, None, None, None]         # (T,1,q,k)
        s = jnp.einsum("tqhd,tkhd->thqk", qh, kb[idx], precision=hi)
        s = jnp.where(same, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        p = jnp.where(same, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        return (m_new, l * corr + p.sum(-1, keepdims=True),
                acc * corr + jnp.einsum("thqk,tkhd->thqd", p, vb[idx],
                                        precision=hi))

    init = (jnp.full((tiles, num_heads, block, 1), NEG_INF),
            jnp.zeros((tiles, num_heads, block, 1)),
            jnp.zeros((tiles, num_heads, block, dh)))
    _, l, acc = jax.lax.fori_loop(0, jnp.maximum(jnp.max(count), 1), step,
                                  init)
    out = acc / jnp.maximum(l, 1e-30)
    return out.transpose(0, 2, 1, 3).reshape(n, d)


def segment_attention(q, k, v, graph_id, graph_num_nodes, *,
                      num_heads: int, block: int = SEGMENT_BLOCK,
                      use_kernel: bool | None = None):
    """Multi-head self-attention of the rows of a packed batch, each
    over the rows of its own graph. q, k, v: (N, H*dh); graph_id (N,),
    ``len(graph_num_nodes)`` on padding rows, whose output is 0.

    The Pallas kernel runs where the backend is a TPU
    (``use_kernel=None``), the same computation in XLA elsewhere. Both
    visit, per ``block``-row query tile, the key blocks
    ``segment_tiles`` names, in as many steps as the batch's widest
    tile needs, so a graph of any size is attended whole."""
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    n, d = q.shape
    num_graphs = graph_num_nodes.shape[0]
    pad = (-n) % block
    if pad:
        q, k, v = (jnp.pad(a, ((0, pad), (0, 0))) for a in (q, k, v))
        graph_id = jnp.pad(graph_id, (0, pad), constant_values=num_graphs)
    first, count = segment_tiles(graph_num_nodes, n + pad, block, jnp)
    args = dict(num_heads=num_heads, num_graphs=num_graphs, block=block)
    if use_kernel:
        out = segment_attention_pallas(q, k, v, graph_id, first, count,
                                       interpret=False, **args)
    else:
        out = _segment_attention_xla(q, k, v, graph_id.astype(jnp.int32),
                                     first, count, **args)
    return out[:n]


def count_segment_attention(graph_num_nodes, num_rows: int,
                            block: int = SEGMENT_BLOCK):
    """Host counters of the segment attention a packed batch of
    ``num_rows`` rows gives (per head, whatever runs it): ``attn.rows``
    (real nodes, the sum of n_g), ``attn.pairs`` (real query-key pairs,
    the sum of n_g^2) and ``attn.pairs_computed`` (the pairs of the key
    blocks the kernel's table sends each query tile to). Counts only
    while a profiler session runs, under its own span
    ``pack.attn_counts``, so that the packer's self time leaves the
    counting out."""
    if not trace.enabled():
        return
    with trace.span("pack.attn_counts"):
        sizes = np.asarray(graph_num_nodes, np.int64)
        _, count = segment_tiles(sizes, num_rows + (-num_rows) % block,
                                 block)
        trace.count("attn.rows", int(sizes.sum()))
        trace.count("attn.pairs", int(np.square(sizes).sum()))
        trace.count("attn.pairs_computed",
                    int(count.astype(np.int64).sum()) * block * block)
