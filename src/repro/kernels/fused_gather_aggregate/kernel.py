"""Pallas TPU kernel: fused gather -> phi -> aggregate over packed COO.

GNNBuilder's core dataflow claim (paper SV-A, Fig. 3) is that messages
*stream* through the gather -> phi -> aggregate pipeline instead of being
materialized. The `segment_aggregate` kernel (PR 2) fused only the
aggregate stage: every conv still wrote an (E, F) message tensor to HBM
via `jnp.take` before reducing it. This kernel closes that seam for the
linear-phi family (GCN / SAGE / GIN-without-edge-MLP): it consumes the
node-feature table (N, F) plus the raw `src`/`dst` edge-id streams and an
optional per-edge scale (the GCN 1/sqrt(d_u d_v) norm), gathers source
rows *inside* the edge-block loop, and folds them straight into the VMEM
node accumulator — the (E, F) message tensor never touches HBM.

Grid: (node_tiles, edge_tiles) — the edge axis is innermost/sequential,
so each node tile's accumulator persists in VMEM across the whole edge
stream (same schedule as `segment_aggregate`). Block shapes:
  x     (N, F)   — the full node-feature table, resident across steps
  src   (1, EB)  — source node ids (-1 = padding, gathers a zero row)
  dst   (1, EB)  — destination ids (-1 = padding, matches no node row)
  scale (1, EB)  — per-edge message scale (1.0 when unused, 0 on padding)
  out   (NB, F)  — this node tile's aggregate (revisited across j)
Scratch: count (NB, 1).

The gather itself is routed through the MXU: a (N, EB) source one-hot
(with the edge scale folded in, so phi costs nothing extra) contracted
against the node table yields the edge block's scaled messages without a
serial gather loop; the scatter side reuses the segment kernel's
destination one-hot matmul / fori-loop updates.

The node table is dtype-polymorphic: fp32, bf16, or int8 tables stay
resident in VMEM at their storage width — the PrecisionPolicy bandwidth
lever for the gather stage — and the gather contraction + every
accumulator run in fp32 (int8 values are integer-valued fp32, so the
accumulation is exact int32-style). For int8 tables the per-tensor
dequantization scale is folded into the per-edge ``scale`` stream by the
caller (core.aggregations), so dequantization also costs nothing extra.

Supported: sum, mean, min, max — the family GCN/SAGE/GIN lower to.
var/std (PNA towers) and per-edge MLPs keep the materialized path.

Two generations live here (docs/KERNELS.md has the full contract):

* ``fused_gather_aggregate_pallas`` — the **legacy one-hot** gather
  (``gather_mode="onehot"``): the (N, EB) source one-hot contraction
  routes the gather through the MXU, costing O(N * EB * F) MACs per
  edge block and re-sweeping the edge stream once per node tile.
* ``fused_gather_aggregate_v2_pallas`` — the **DMA gather**
  (``gather_mode="dma"``, the default): the src/dst id streams are
  scalar-prefetched into SMEM (PrefetchScalarGridSpec) together with
  the per-edge scale stream, node rows are gathered by dynamic slice —
  O(EB * F) work per edge block, one sweep over the edge stream, no
  one-hot ever materialized. This is the kernel the chip path compiles.

Mosaic compiles the one-hot kernel for sum/mean only: its min/max
masked scatter needs an (NB, EB, 1) mask reshape that Mosaic refuses, so
a compiled (non-interpret) one-hot min/max raises before lowering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.aggregations import resolve_interpret
from repro.kernels.tpu import (LANES, compiler_params, edge_tile,
                               pad_lanes)

AGGS = ("sum", "mean", "min", "max")
# what Mosaic compiles of the legacy one-hot kernel (module docstring)
ONEHOT_COMPILED_AGGS = ("sum", "mean")


def _fused_kernel(x_ref, src_ref, dst_ref, scale_ref, out_ref, cnt_ref, *,
                  agg: str, edge_steps: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    nb = out_ref.shape[0]
    n_src = x_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        if agg in ("sum", "mean"):
            out_ref[...] = jnp.zeros_like(out_ref)
        elif agg == "min":
            out_ref[...] = jnp.full(out_ref.shape, jnp.inf, out_ref.dtype)
        else:
            out_ref[...] = jnp.full(out_ref.shape, -jnp.inf, out_ref.dtype)

    # gather prologue: (N, EB) source one-hot with the per-edge scale
    # folded in, contracted against the node table on the MXU. Padding
    # edges (src == -1) match no row and gather an all-zero message.
    node_rows = jax.lax.broadcasted_iota(jnp.int32, (n_src, 1), 0)
    src_onehot = (src_ref[...] == node_rows).astype(jnp.float32) \
        * scale_ref[...].astype(jnp.float32)
    msg = jax.lax.dot_general(
        src_onehot, x_ref[...].astype(jnp.float32),
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # (EB, F)

    # (NB, EB) edge->node assignment for this tile pair; padding edges
    # carry dst == -1 and match no node row.
    node_ids = i * nb + jax.lax.broadcasted_iota(jnp.int32, (nb, 1), 0)
    onehot = dst_ref[...] == node_ids

    if agg in ("sum", "mean"):
        onef = onehot.astype(jnp.float32)
        out_ref[...] += jnp.dot(onef, msg,
                                preferred_element_type=jnp.float32)
        cnt_ref[...] += jnp.sum(onef, axis=1, keepdims=True)
    else:
        # vectorized masked scatter: broadcast the (NB, EB) assignment
        # over the feature axis and reduce the edge axis in one VPU
        # expression — unassigned (node, edge) pairs contribute the
        # neutral element, so the whole block folds in at once instead
        # of a per-edge serial fori_loop
        neutral = jnp.inf if agg == "min" else -jnp.inf
        masked = jnp.where(onehot[:, :, None], msg[None], neutral)
        blk = masked.min(axis=1) if agg == "min" else masked.max(axis=1)
        out_ref[...] = jnp.minimum(out_ref[...], blk) if agg == "min" \
            else jnp.maximum(out_ref[...], blk)
        cnt_ref[...] += jnp.sum(onehot.astype(jnp.float32), axis=1,
                                keepdims=True)

    @pl.when(j == edge_steps - 1)
    def _finalize():
        if agg == "mean":
            out_ref[...] = out_ref[...] / jnp.maximum(cnt_ref[...], 1.0)
        elif agg in ("min", "max"):
            o = out_ref[...]
            out_ref[...] = jnp.where(jnp.isfinite(o), o, 0.0)


def fused_gather_aggregate_pallas(x, src, dst, num_segments: int, *,
                                  scale=None, agg: str = "sum",
                                  edge_block: int = 128,
                                  node_block: int = 128,
                                  interpret: bool | None = None):
    """x: (N, F) node features in fp32, bf16, or int8 (the table streams
    and stays VMEM-resident at its storage width; accumulation is fp32);
    src/dst: (E,) int32 endpoint id streams of the packed COO edge
    buffer (-1 or any out-of-range id = padding); scale: optional (E,)
    per-edge message scale (phi), applied before aggregation — int8
    callers fold the dequant scale in here. Returns (num_segments, F)
    float32 aggregates; empty segments zero-fill. The (E, F) message
    tensor is never materialized.
    """
    assert agg in AGGS, agg
    interpret = resolve_interpret(interpret)
    if not interpret and agg not in ONEHOT_COMPILED_AGGS:
        raise NotImplementedError(
            f"the one-hot gather does not compile {agg!r} under Mosaic; "
            "use gather_mode='dma'")
    n_src, f = x.shape
    e = src.shape[0]
    if e == 0 or num_segments == 0:
        return jnp.zeros((num_segments, f), jnp.float32)
    src = src.astype(jnp.int32)
    dst = dst.astype(jnp.int32)
    # out-of-range ids (packed-batch overflow bucket, -1 padding) are
    # normalized to -1 on *both* streams so a bad edge neither gathers
    # nor scatters
    bad = (src < 0) | (src >= n_src) | (dst < 0) | (dst >= num_segments)
    src = jnp.where(bad, -1, src)
    dst = jnp.where(bad, -1, dst)
    if scale is None:
        scale = jnp.ones((e,), jnp.float32)
    scale = jnp.where(bad, 0.0, scale.astype(jnp.float32))
    eb = min(edge_block, e)
    nb = min(node_block, num_segments)
    e_pad = (-e) % eb
    n_pad = (-num_segments) % nb
    if e_pad:
        src = jnp.pad(src, (0, e_pad), constant_values=-1)
        dst = jnp.pad(dst, (0, e_pad), constant_values=-1)
        scale = jnp.pad(scale, (0, e_pad))
    grid = ((num_segments + n_pad) // nb, (e + e_pad) // eb)
    out = pl.pallas_call(
        functools.partial(_fused_kernel, agg=agg, edge_steps=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_src, f), lambda i, j: (0, 0)),
            pl.BlockSpec((1, eb), lambda i, j: (0, j)),
            pl.BlockSpec((1, eb), lambda i, j: (0, j)),
            pl.BlockSpec((1, eb), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((nb, f), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((num_segments + n_pad, f),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((nb, 1), jnp.float32)],
        compiler_params=compiler_params(),
        interpret=interpret,
    )(x, src.reshape(1, e + e_pad),
      dst.reshape(1, e + e_pad), scale.reshape(1, e + e_pad))
    return out[:num_segments]


# ------------------------------------------------------------ gather v2 --
def _v2_kernel(src_ref, dst_ref, scale_ref, x_ref, out_ref, cnt_ref,
               xf_ref, *, agg: str, edge_steps: int, eb: int,
               track_count: bool, upcast: bool):
    """One grid step folds one edge block into the resident accumulator.

    src_ref/dst_ref/scale_ref are the *whole* per-edge streams in SMEM
    (scalar prefetch), read one scalar per edge. x_ref and out_ref are
    whole-table VMEM residents; a bf16/int8 table is widened once into
    the fp32 ``xf_ref`` shadow at step 0, because Mosaic cannot slice
    single rows out of a packed (sub-32-bit) tile."""
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        if track_count:
            cnt_ref[...] = jnp.zeros_like(cnt_ref)
        if agg in ("sum", "mean"):
            out_ref[...] = jnp.zeros_like(out_ref)
        elif agg == "min":
            out_ref[...] = jnp.full(out_ref.shape, jnp.inf, out_ref.dtype)
        else:
            out_ref[...] = jnp.full(out_ref.shape, -jnp.inf, out_ref.dtype)
        if upcast:
            xf_ref[...] = x_ref[...].astype(jnp.float32)

    table = xf_ref if upcast else x_ref
    base = j * eb

    def body(e, _):
        s = src_ref[base + e]
        d = dst_ref[base + e]
        sl = jnp.maximum(s, 0)
        dl = jnp.maximum(d, 0)
        sc = scale_ref[base + e]
        row = table[pl.ds(sl, 1), :].astype(jnp.float32) * sc
        cur = out_ref[pl.ds(dl, 1), :]
        if agg in ("sum", "mean"):
            # padding edges carry scale == 0: they add a zero row at the
            # clamped slot, so no validity select is needed on this path
            out_ref[pl.ds(dl, 1), :] = cur + row
        else:
            ok = d >= 0
            upd = jnp.minimum(cur, row) if agg == "min" \
                else jnp.maximum(cur, row)
            out_ref[pl.ds(dl, 1), :] = jnp.where(ok, upd, cur)
        if track_count:
            c = cnt_ref[pl.ds(dl, 1), :]
            cnt_ref[pl.ds(dl, 1), :] = c + jnp.where(d >= 0, 1.0, 0.0)
        return 0

    jax.lax.fori_loop(0, eb, body, 0)

    @pl.when(j == edge_steps - 1)
    def _finalize():
        if agg == "mean":
            out_ref[...] = out_ref[...] / jnp.maximum(cnt_ref[...], 1.0)
        elif agg in ("min", "max"):
            o = out_ref[...]
            out_ref[...] = jnp.where(jnp.isfinite(o), o, 0.0)


def fused_gather_aggregate_v2_pallas(x, src, dst, num_segments: int, *,
                                     scale=None, agg: str = "sum",
                                     edge_block: int = 128,
                                     node_block: int = 128,
                                     interpret: bool | None = None):
    """One-hot-free fused gather (``gather_mode="dma"``, the default).

    Same contract as ``fused_gather_aggregate_pallas`` — x: (N, F) node
    table in fp32/bf16/int8 (VMEM-resident at storage width, fp32
    accumulation); src/dst: (E,) int32 with -1/out-of-range = padding;
    scale: optional (E,) per-edge phi (int8 dequant folds in here);
    returns (num_segments, F) float32, empty segments zero-fill — but a
    different machine: the id streams ride in SMEM via scalar prefetch,
    each source row is gathered by dynamic slice (O(EB * F) per edge
    block instead of the one-hot's O(N * EB * F)), the per-edge scale
    rides SMEM beside them (one scalar read per edge), and the whole
    (num_segments, F) accumulator is VMEM-resident, so the edge stream
    is swept exactly once (``node_block`` is accepted for knob
    compatibility and ignored). F is zero-padded to a multiple of 128
    lanes inside and sliced back on the way out.

    Grid: (edge_tiles,). Scalar prefetch: src, dst and scale streams.
    Scratch: the mean path's (num_segments, 1) count column + the fp32
    shadow of a bf16/int8 table.
    """
    assert agg in AGGS, agg
    del node_block                       # v2 keeps the whole table
    n_src, f = x.shape
    e = src.shape[0]
    if e == 0 or num_segments == 0:
        return jnp.zeros((num_segments, f), jnp.float32)
    src = src.astype(jnp.int32)
    dst = dst.astype(jnp.int32)
    bad = (src < 0) | (src >= n_src) | (dst < 0) | (dst >= num_segments)
    src = jnp.where(bad, -1, src)
    dst = jnp.where(bad, -1, dst)
    if scale is None:
        scale = jnp.ones((e,), jnp.float32)
    scale = jnp.where(bad, 0.0, scale.astype(jnp.float32))
    eb = edge_tile(e, edge_block)
    e_pad = (-e) % eb
    if e_pad:
        src = jnp.pad(src, (0, e_pad), constant_values=-1)
        dst = jnp.pad(dst, (0, e_pad), constant_values=-1)
        scale = jnp.pad(scale, (0, e_pad))
    x = pad_lanes(x)
    fp = x.shape[1]
    steps = (e + e_pad) // eb
    track_count = agg == "mean"
    upcast = x.dtype != jnp.float32
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(steps,),
        in_specs=[pl.BlockSpec((n_src, fp), lambda j, *_: (0, 0))],
        out_specs=pl.BlockSpec((num_segments, fp), lambda j, *_: (0, 0)),
        scratch_shapes=[
            pltpu.VMEM((num_segments if track_count else 8, 1),
                       jnp.float32),
            pltpu.VMEM((n_src, fp) if upcast else (8, LANES),
                       jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_v2_kernel, agg=agg, edge_steps=steps, eb=eb,
                          track_count=track_count, upcast=upcast),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_segments, fp), jnp.float32),
        compiler_params=compiler_params(),
        interpret=resolve_interpret(interpret),
    )(src, dst, scale, x)
    return out[:, :f]
