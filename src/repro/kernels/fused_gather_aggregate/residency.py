"""Pallas TPU kernel: multi-layer VMEM-resident conv stack.

The packed GraphBatch IR makes layer boundaries explicit, so for the
linear-phi family (GCN / SAGE without edge features) consecutive conv
layers can run back-to-back *inside one kernel*: the node-feature table
is written to VMEM once, every layer's gather -> aggregate -> transform
-> skip -> activation executes against the resident table, and HBM sees
the table exactly twice (initial copy-in, final copy-out) instead of
twice **per layer** — the inter-layer on-chip reuse lever of the
GNN-acceleration survey (PAPERS.md, 2306.14052), and the TPU analogue of
keeping the embedding BRAM hot across the paper's pipelined layers.

Grid: (layers, edge_tiles) — the edge axis is innermost/sequential, so
each layer sweeps the whole edge stream before the next layer's grid
steps begin. Blocks:
  x0     (N, Fmax)      — initial node table, read once (copy-in at
                          step (0, 0))
  sv     (N, 1)         — GCN self-loop scale (unused for SAGE)
  mask   (N, 1)         — node validity column
  w_a/w_n/w_skip (1, Fmax, Fmax), b (1, 1, Fmax) — layer i's stacked
                          zero-padded weights (skip: identity when the
                          dims match, the projection when they differ,
                          zeros when skips are off); Pallas streams the
                          per-layer blocks double-buffered
  out    (N, Fmax)      — the resident table, revisited by every step
Scalar prefetch (SMEM): the src/dst id streams, the per-edge phi
(GCN norm / SAGE validity; 0 on padding), and the flattened (K, 4)
per-layer precision rows [mode, s, lo, hi].
Scratch: aggr (N, Fmax) accumulator, count column (mean only), and the
quantized-table shadow xq (non-fp32 policies only).

Per-layer math (the exact ``core.convs`` aggregate-first forms):
  GCN:  h = round((aggr + xq * sv)) @ W + b
  SAGE: h = round(xq) @ W_self + b_self + round(aggr) @ W_neigh
then h (+ skip from the *fp32* table) -> activation -> node mask, and
the result overwrites the resident table for the next layer. ``round``
/ ``xq`` emulate the per-layer PrecisionPolicy dynamically from the qp
row: mode 0 = fp32 identity, 1 = bf16 rounding, 2 = int8 fake-quant
(``clip(round(x / s) * s)`` — exactly ``quantization.quantize``; the
shadow table stores grid values at fp32 emulation width, as the XLA
path does). Zero-padded weight columns keep padded feature columns from
ever leaking into real ones, so the caller just slices the final table.

Padding edges (src == -1 after normalization) carry scale == 0 and are
excluded from the mean count; min/max are not needed here (GCN lowers
to sum, SAGE to mean), so the accumulator is a plain fp32 add.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.aggregations import resolve_interpret
from repro.kernels.tpu import compiler_params, edge_tile
from repro.nn.layers import act

RESIDENT_KINDS = ("gcn", "sage")
RESIDENT_AGGS = {"gcn": "sum", "sage": "mean"}

# qp-row precision modes (matching quantization.PRECISIONS order)
_MODE_FP32, _MODE_BF16, _MODE_INT8 = 0.0, 1.0, 2.0
QP_WIDTH = 4


def _cast_dyn(x, qp):
    """Dynamic ``LayerPrecision.cast_activation``: qp = (mode, s, lo, hi)
    scalars.
    All three candidates are cheap VPU expressions; ``where`` selects the
    layer's mode at run time so one kernel serves mixed-precision
    stacks."""
    mode, s, lo, hi = qp[0], qp[1], qp[2], qp[3]
    bf = x.astype(jnp.bfloat16).astype(jnp.float32)
    safe_s = jnp.maximum(s, 1e-30)
    i8 = jnp.clip(jnp.round(x / safe_s) * safe_s, lo, hi)
    return jnp.where(mode == _MODE_BF16, bf,
                     jnp.where(mode == _MODE_INT8, i8, x))


def _round_in(x, qp):
    """Dynamic mirror of ``aggr.astype(x_in.dtype)`` before the conv
    matmul: bf16 rounds, fp32/int8 (fake-quant values live in fp32) pass
    through."""
    bf = x.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.where(qp[0] == _MODE_BF16, bf, x)


def _stack_kernel(src_ref, dst_ref, scale_ref, qp_ref, x0_ref, sv_ref,
                  mask_ref, wa_ref, wn_ref, wsk_ref, b_ref, xout_ref,
                  aggr_ref, cnt_ref, xq_ref, *, kind: str,
                  activation: str, edge_steps: int, eb: int,
                  has_skip: bool, quantized: bool):
    i = pl.program_id(0)
    j = pl.program_id(1)
    track_count = RESIDENT_AGGS[kind] == "mean"
    qp = tuple(qp_ref[i * QP_WIDTH + c] for c in range(QP_WIDTH))

    @pl.when((i == 0) & (j == 0))
    def _copy_in():
        xout_ref[...] = x0_ref[...].astype(jnp.float32)

    @pl.when(j == 0)
    def _layer_init():
        aggr_ref[...] = jnp.zeros_like(aggr_ref)
        if track_count:
            cnt_ref[...] = jnp.zeros_like(cnt_ref)
        if quantized:
            xq_ref[...] = _cast_dyn(xout_ref[...], qp)

    table_ref = xq_ref if quantized else xout_ref
    base = j * eb

    def body(e, _):
        s = src_ref[base + e]
        d = dst_ref[base + e]
        sl = jnp.maximum(s, 0)
        dl = jnp.maximum(d, 0)
        sc = scale_ref[base + e]
        row = table_ref[pl.ds(sl, 1), :] * sc
        aggr_ref[pl.ds(dl, 1), :] += row     # padding: scale == 0
        if track_count:
            c = cnt_ref[pl.ds(dl, 1), :]
            cnt_ref[pl.ds(dl, 1), :] = c + jnp.where(d >= 0, 1.0, 0.0)
        return 0

    jax.lax.fori_loop(0, eb, body, 0)

    @pl.when(j == edge_steps - 1)
    def _layer_boundary():
        aggr = aggr_ref[...]
        if track_count:
            aggr = aggr / jnp.maximum(cnt_ref[...], 1.0)
        xq = table_ref[...]
        w_n = wn_ref[0]
        bias = b_ref[0]
        if kind == "gcn":
            t = _round_in(aggr + xq * sv_ref[...], qp)
            h = jnp.dot(t, w_n, preferred_element_type=jnp.float32) + bias
        else:                                # sage
            h = jnp.dot(_round_in(xq, qp), wa_ref[0],
                        preferred_element_type=jnp.float32) + bias \
                + jnp.dot(_round_in(aggr, qp), w_n,
                          preferred_element_type=jnp.float32)
        h = _round_in(h, qp)                 # conv output at compute width
        if has_skip:
            # skips run on the fp32 residual stream (pre-cast table)
            h = h + jnp.dot(xout_ref[...], wsk_ref[0],
                            preferred_element_type=jnp.float32)
        xout_ref[...] = act(activation)(h) * mask_ref[...]


def fused_layer_stack_pallas(x, src, dst, scale, self_vec, node_mask,
                             w_a, w_n, w_skip, b, qp, *, kind: str,
                             activation: str = "relu",
                             edge_block: int = 128,
                             interpret: bool | None = None,
                             has_skip: bool = True,
                             quantized: bool = False):
    """Run ``K = w_n.shape[0]`` consecutive conv layers with the node
    table VMEM-resident. x: (N, Fmax) fp32 zero-padded table; src/dst:
    (E,) int32 (-1 / out-of-range = padding); scale: (E,) per-edge phi;
    self_vec / node_mask: (N, 1) fp32; w_a/w_n/w_skip: (K, Fmax, Fmax)
    zero-padded stacks, b: (K, Fmax); qp: (K, 4) per-layer precision
    rows [mode, s, lo, hi]. Returns the (N, Fmax) fp32 table after the
    last layer (callers slice to the final out_dim). Scoped VMEM is
    ``kernels.tpu.VMEM_LIMIT_BYTES``, the budget
    ``core.convs.residency_plan`` checks the working set against."""
    if kind not in RESIDENT_KINDS:
        raise ValueError(f"resident stack supports {RESIDENT_KINDS}, "
                         f"got {kind!r}")
    n, fmax = x.shape
    k = w_n.shape[0]
    e = src.shape[0]
    src = jnp.asarray(src).astype(jnp.int32)
    dst = jnp.asarray(dst).astype(jnp.int32)
    bad = (src < 0) | (src >= n) | (dst < 0) | (dst >= n)
    src = jnp.where(bad, -1, src)
    dst = jnp.where(bad, -1, dst)
    scale = jnp.where(bad, 0.0, scale.astype(jnp.float32))
    eb = edge_tile(e, edge_block)
    e_pad = (-e) % eb if e else eb
    if e_pad:
        src = jnp.pad(src, (0, e_pad), constant_values=-1)
        dst = jnp.pad(dst, (0, e_pad), constant_values=-1)
        scale = jnp.pad(scale, (0, e_pad))
    steps = (e + e_pad) // eb
    qp = jnp.asarray(qp, jnp.float32)[:, :QP_WIDTH].reshape(-1)
    layer_block = pl.BlockSpec((1, fmax, fmax), lambda i, j, *_: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(k, steps),
        in_specs=[
            pl.BlockSpec((n, fmax), lambda i, j, *_: (0, 0)),
            pl.BlockSpec((n, 1), lambda i, j, *_: (0, 0)),
            pl.BlockSpec((n, 1), lambda i, j, *_: (0, 0)),
            layer_block, layer_block, layer_block,
            pl.BlockSpec((1, 1, fmax), lambda i, j, *_: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((n, fmax), lambda i, j, *_: (0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n, fmax), jnp.float32),
            pltpu.VMEM((n if RESIDENT_AGGS[kind] == "mean" else 8, 1),
                       jnp.float32),
            pltpu.VMEM((n, fmax) if quantized else (8, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_stack_kernel, kind=kind, activation=activation,
                          edge_steps=steps, eb=eb, has_skip=has_skip,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, fmax), jnp.float32),
        compiler_params=compiler_params(),
        interpret=resolve_interpret(interpret),
    )(src, dst, scale, qp, x.astype(jnp.float32),
      self_vec.astype(jnp.float32).reshape(n, 1),
      node_mask.astype(jnp.float32).reshape(n, 1),
      w_a.astype(jnp.float32), w_n.astype(jnp.float32),
      w_skip.astype(jnp.float32), b.astype(jnp.float32).reshape(k, 1, fmax))
