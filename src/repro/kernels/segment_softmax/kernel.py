"""Pallas TPU kernel: numerically stable per-segment softmax statistics
over the packed COO edge stream.

Attention convs (GAT) normalize per-edge logits within each
*destination* segment: ``alpha_e = exp(z_e - m_dst) / sum_e' exp(...)``.
The reduction shape is the same as ``segment_aggregate`` — one pass over
the edge stream folding into a VMEM-resident per-segment table — but the
state machine is the online softmax of ``kernels/flash_attention``: a
running max ``m`` and a running exp-sum ``l`` corrected by
``exp(m_prev - m_new)`` whenever the max moves, so ``exp`` never sees a
positive argument regardless of logit magnitude (the +-1e4 stability
contract, docs/KERNELS.md).

The kernel produces the per-segment (max, denominator) tables; the
per-edge normalization ``exp(z - m[seg]) / max(l[seg], tiny)`` is a
cheap elementwise gather done by the caller (``segment_softmax_pallas``)
— per-edge *outputs* would otherwise force a second DMA sweep for what
XLA already fuses.

Masking: seg_ids carry -1 (or out-of-range ids) on padding edges; a
-inf logit on a *valid* edge is a masked attention slot — it contributes
``exp(-inf) == 0`` to the denominator and gets weight 0 without ever
producing a NaN (the running max is clamped at ``NEG_INF = -1e30``, so
the kernel never evaluates ``-inf - (-inf)``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.aggregations import resolve_interpret
from repro.kernels.tpu import compiler_params, edge_tile

NEG_INF = -1e30     # finite "empty" max: keeps -inf logits NaN-free
TINY = 1e-30        # denominator floor (empty segments divide by this)


def _softmax_stats_kernel(seg_ref, logit_ref, m_ref, l_ref, *, eb: int):
    """One grid step folds one edge block into the resident (m, l)
    tables. The seg-id and logit streams both ride SMEM via scalar
    prefetch (one scalar read each per edge), and both per-segment
    tables stay VMEM-resident across the single edge sweep."""
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, m_ref.dtype)
        l_ref[...] = jnp.zeros_like(l_ref)

    base = j * eb

    def body(e, _):
        d = seg_ref[base + e]
        dl = jnp.maximum(d, 0)
        ok = d >= 0
        z = logit_ref[base + e]
        m_prev = m_ref[pl.ds(dl, 1), :]
        # the running max never drops below NEG_INF, so a -inf logit
        # leaves it unchanged and exp(m_prev - m_new) stays exp(0) = 1
        m_new = jnp.maximum(m_prev, z)
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(z - m_new)
        l_prev = l_ref[pl.ds(dl, 1), :]
        m_ref[pl.ds(dl, 1), :] = jnp.where(ok, m_new, m_prev)
        l_ref[pl.ds(dl, 1), :] = jnp.where(ok, l_prev * corr + p, l_prev)
        return 0

    jax.lax.fori_loop(0, eb, body, 0)


def segment_softmax_stats_pallas(logits, seg_ids, num_segments: int, *,
                                 edge_block: int = 128,
                                 interpret: bool | None = None):
    """Per-segment online-softmax statistics over a packed edge stream.

    logits: (E,) float; seg_ids: (E,) int32 with -1 / out-of-range =
    padding. Returns ``(m, l)``: (num_segments,) float32 running max
    (NEG_INF for empty segments) and exp-sum denominator (0 for empty
    segments). Grid: (edge_tiles,); scalar prefetch: the seg-id and
    logit streams; both output tables VMEM-resident."""
    e = logits.shape[0]
    if e == 0 or num_segments == 0:
        return (jnp.full((num_segments,), NEG_INF, jnp.float32),
                jnp.zeros((num_segments,), jnp.float32))
    seg_ids = seg_ids.astype(jnp.int32)
    seg_ids = jnp.where((seg_ids >= 0) & (seg_ids < num_segments),
                        seg_ids, -1)
    z = logits.astype(jnp.float32)
    eb = edge_tile(e, edge_block)
    e_pad = (-e) % eb
    if e_pad:
        z = jnp.pad(z, (0, e_pad))
        seg_ids = jnp.pad(seg_ids, (0, e_pad), constant_values=-1)
    steps = (e + e_pad) // eb
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(steps,),
        in_specs=[],
        out_specs=[
            pl.BlockSpec((num_segments, 1), lambda j, *_: (0, 0)),
            pl.BlockSpec((num_segments, 1), lambda j, *_: (0, 0)),
        ],
    )
    m, l = pl.pallas_call(
        functools.partial(_softmax_stats_kernel, eb=eb),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((num_segments, 1), jnp.float32),
            jax.ShapeDtypeStruct((num_segments, 1), jnp.float32),
        ],
        compiler_params=compiler_params(),
        interpret=resolve_interpret(interpret),
    )(seg_ids, z)
    return m[:, 0], l[:, 0]


def segment_softmax_pallas(logits, seg_ids, num_segments: int, *,
                           edge_block: int = 128,
                           interpret: bool | None = None):
    """Per-edge softmax weights normalized within each segment.

    logits: (E,); seg_ids: (E,) with -1 / out-of-range = padding.
    Returns (E,) float32: rows of each non-empty segment sum to 1;
    padding edges, -inf-masked logits, and members of all-masked
    segments get exactly 0 — never NaN/Inf."""
    seg_ids = jnp.asarray(seg_ids, jnp.int32)
    seg_ids = jnp.where((seg_ids >= 0) & (seg_ids < num_segments),
                        seg_ids, -1)
    m, l = segment_softmax_stats_pallas(
        logits, seg_ids, num_segments, edge_block=edge_block,
        interpret=interpret)
    ok = seg_ids >= 0
    sl = jnp.maximum(seg_ids, 0)
    z = logits.astype(jnp.float32)
    # padding logits can exceed their (clamped) segment max, so exp may
    # overflow to +inf on lanes the where() discards — mask first
    p = jnp.where(ok, jnp.exp(jnp.where(ok, z, NEG_INF)
                              - jnp.take(m, sl)), 0.0)
    return p / jnp.maximum(jnp.take(l, sl), TINY)
