"""Pallas TPU kernel: fused edge-segment aggregation over packed COO.

The packed GraphBatch IR (DESIGN_BATCHING.md) carries edges as a flat COO
stream — messages (E, F) plus per-edge destination segment ids — which is
the layout the paper's message-passing engine (Fig. 3) consumes: a sorted
edge stream driving single-pass partial aggregations (§V-B). This kernel
is the TPU analogue of that datapath for *packed* batches: the node
accumulator table lives in VMEM scratch (the BRAM analogue), the edge
stream is tiled into ``edge_block``-sized chunks, and each grid step folds
one chunk into the table. var/std use Welford's online update, identical
math to the streaming reference in ``core.aggregations``.

Grid: (node_tiles, edge_tiles) — the edge axis is innermost/sequential,
so each node tile's accumulator persists in VMEM across the whole edge
stream. Block shapes:
  msg   (EB, F)  — this step's edge messages
  dst   (1, EB)  — destination segment ids (-1 = padding, never matches)
  out   (NB, F)  — this node tile's aggregate (revisited across j)
Scratch: count (NB, 1) always; Welford mean/M2 (NB, F) for var/std.

The kernel is dtype-polymorphic in the *message tiles*: fp32, bf16, or
int8 blocks move HBM->VMEM at their storage width (the PrecisionPolicy
bandwidth lever), and every accumulator — sum, count, Welford mean/M2 —
is fp32 regardless (int8 sums are integer-valued fp32, i.e. exact
int32-style accumulation). Low-precision inputs are dequantized by the
caller (core.aggregations folds the per-tensor scale onto the output);
the output is always fp32.

Two generations live here, mirroring ``fused_gather_aggregate``
(docs/KERNELS.md has the full contract):

* ``segment_aggregate_pallas`` — the **legacy one-hot** schedule
  (``gather_mode="onehot"``): a (NB, EB) destination one-hot routes the
  scatter through the MXU / a masked VPU reduce, costing O(NB * EB * F)
  per tile pair and re-sweeping the edge stream once per node tile.
* ``segment_aggregate_v2_pallas`` — the **DMA** schedule
  (``gather_mode="dma"``, the default): the dst id stream is
  scalar-prefetched into SMEM (PrefetchScalarGridSpec), message tiles
  are double-buffered HBM->VMEM by explicit async copies at storage
  width, and the whole (num_segments, F) accumulator — including the
  Welford mean/M2 pair for var/std — is VMEM-resident, so the edge
  stream is swept exactly once with no one-hot ever materialized.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.aggregations import resolve_interpret
from repro.kernels.tpu import compiler_params, edge_tile, pad_lanes

AGGS = ("sum", "mean", "min", "max", "var", "std")
# what Mosaic compiles of the legacy one-hot kernel: min/max need an
# (NB, EB, 1) mask reshape and var/std a dynamic_slice, both refused
ONEHOT_COMPILED_AGGS = ("sum", "mean")


def _seg_kernel(msg_ref, dst_ref, out_ref, *scratch, agg: str,
                edge_steps: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    nb, f = out_ref.shape
    eb = msg_ref.shape[0]
    cnt_ref = scratch[0]

    @pl.when(j == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        if agg in ("sum", "mean"):
            out_ref[...] = jnp.zeros_like(out_ref)
        elif agg == "min":
            out_ref[...] = jnp.full(out_ref.shape, jnp.inf, out_ref.dtype)
        elif agg == "max":
            out_ref[...] = jnp.full(out_ref.shape, -jnp.inf, out_ref.dtype)
        else:                                   # Welford mean / M2
            scratch[1][...] = jnp.zeros_like(scratch[1])
            scratch[2][...] = jnp.zeros_like(scratch[2])

    # (NB, EB) edge->node assignment for this tile pair; padding edges
    # carry dst == -1 and match no node row.
    node_ids = i * nb + jax.lax.broadcasted_iota(jnp.int32, (nb, 1), 0)
    onehot = dst_ref[...] == node_ids
    msg = msg_ref[...].astype(jnp.float32)

    if agg in ("sum", "mean"):
        # scatter-add as a matmul: the MXU does the routing
        onef = onehot.astype(jnp.float32)
        out_ref[...] += jnp.dot(onef, msg,
                                preferred_element_type=jnp.float32)
        cnt_ref[...] += jnp.sum(onef, axis=1, keepdims=True)
    elif agg in ("min", "max"):
        # vectorized masked scatter (same shape as the fused kernel's):
        # unassigned (node, edge) pairs contribute the neutral element,
        # so one (NB, EB, F) where + edge-axis reduce replaces the
        # per-edge serial fori_loop
        neutral = jnp.inf if agg == "min" else -jnp.inf
        masked = jnp.where(onehot[:, :, None], msg[None], neutral)
        blk = masked.min(axis=1) if agg == "min" else masked.max(axis=1)
        out_ref[...] = jnp.minimum(out_ref[...], blk) if agg == "min" \
            else jnp.maximum(out_ref[...], blk)
        cnt_ref[...] += jnp.sum(onehot.astype(jnp.float32), axis=1,
                                keepdims=True)
    else:
        # Welford single-pass (paper §V-B): O(1) state per node row
        mean_ref, m2_ref = scratch[1], scratch[2]

        def body(e, state):
            mean, m2, cnt = state
            sel = jax.lax.dynamic_slice(onehot, (0, e), (nb, 1))
            row = jax.lax.dynamic_slice(msg, (e, 0), (1, f))
            cnt_new = cnt + sel.astype(jnp.float32)
            safe = jnp.maximum(cnt_new, 1.0)
            delta = row - mean
            mean_new = mean + jnp.where(sel, delta / safe, 0.0)
            m2_new = m2 + jnp.where(sel, delta * (row - mean_new), 0.0)
            return mean_new, m2_new, cnt_new
        mean, m2, cnt = jax.lax.fori_loop(
            0, eb, body, (mean_ref[...], m2_ref[...], cnt_ref[...]))
        mean_ref[...] = mean
        m2_ref[...] = m2
        cnt_ref[...] = cnt

    @pl.when(j == edge_steps - 1)
    def _finalize():
        if agg == "mean":
            out_ref[...] = out_ref[...] / jnp.maximum(cnt_ref[...], 1.0)
        elif agg in ("min", "max"):
            o = out_ref[...]
            out_ref[...] = jnp.where(jnp.isfinite(o), o, 0.0)
        elif agg in ("var", "std"):
            var = scratch[2][...] / jnp.maximum(cnt_ref[...], 1.0)
            var = jnp.maximum(var, 1e-12)   # clamp: sqrt'(0)=inf -> NaNs
            out_ref[...] = jnp.sqrt(var) if agg == "std" else var


def segment_aggregate_pallas(messages, seg_ids, num_segments: int, *,
                             agg: str = "sum", edge_block: int = 128,
                             node_block: int = 128,
                             interpret: bool | None = None):
    """messages: (E, F) in fp32, bf16, or int8 — tiles stream at the
    storage width, accumulation is fp32; seg_ids: (E,) int32 destination
    segment per edge, -1 (or any id outside [0, num_segments)) on
    padding. Returns (num_segments, F) float32 aggregates; empty
    segments zero-fill (the var/std clamp floor counts as zero at fp32
    tolerance). Compiled (non-interpret) only for
    ``ONEHOT_COMPILED_AGGS``; the chip path is ``gather_mode="dma"``.
    """
    assert agg in AGGS, agg
    interpret = resolve_interpret(interpret)
    if not interpret and agg not in ONEHOT_COMPILED_AGGS:
        raise NotImplementedError(
            f"the one-hot segment kernel does not compile {agg!r} under "
            "Mosaic; use gather_mode='dma'")
    e, f = messages.shape
    eb = min(edge_block, e)
    nb = min(node_block, num_segments)
    e_pad = (-e) % eb
    n_pad = (-num_segments) % nb
    seg_ids = seg_ids.astype(jnp.int32)
    # out-of-range ids (packed-batch overflow bucket == num_segments, or
    # -1 padding) are normalized to -1 so they match no node row
    seg_ids = jnp.where((seg_ids >= 0) & (seg_ids < num_segments),
                        seg_ids, -1)
    if e_pad:
        messages = jnp.pad(messages, ((0, e_pad), (0, 0)))
        seg_ids = jnp.pad(seg_ids, (0, e_pad), constant_values=-1)
    dst = seg_ids.reshape(1, e + e_pad)
    grid = ((num_segments + n_pad) // nb, (e + e_pad) // eb)
    scratch = [pltpu.VMEM((nb, 1), jnp.float32)]
    if agg in ("var", "std"):
        scratch += [pltpu.VMEM((nb, f), jnp.float32),
                    pltpu.VMEM((nb, f), jnp.float32)]
    out = pl.pallas_call(
        functools.partial(_seg_kernel, agg=agg, edge_steps=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((eb, f), lambda i, j: (j, 0)),
            pl.BlockSpec((1, eb), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((nb, f), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((num_segments + n_pad, f),
                                       jnp.float32),
        scratch_shapes=scratch,
        compiler_params=compiler_params(),
        interpret=interpret,
    )(messages, dst)
    return out[:num_segments]


# ----------------------------------------------------------- segment v2 --
def _seg_v2_kernel(dst_ref, msg_hbm, out_ref, sbuf, sems, cnt_ref,
                   mean_ref, m2_ref, tile_ref, *, agg: str,
                   edge_steps: int, eb: int, upcast: bool):
    """One grid step folds one message tile into the resident table.

    dst_ref is the *whole* id stream in SMEM (scalar prefetch); msg_hbm
    stays in HBM (memory_space=ANY) and is copied one edge block ahead
    of compute through the two slots of ``sbuf`` (a (2, EB, F) VMEM
    scratch at the message storage width) — the double-buffered
    HBM->VMEM edge pipeline. A bf16/int8 tile is widened once into the
    fp32 ``tile_ref`` before the per-edge loop, because Mosaic cannot
    slice single rows out of a packed (sub-32-bit) tile. out_ref and
    the Welford mean/M2 scratch are whole-table VMEM residents, so the
    edge stream is swept once."""
    j = pl.program_id(0)

    def dma(slot, step):
        return pltpu.make_async_copy(
            msg_hbm.at[pl.ds(step * eb, eb), :], sbuf.at[slot],
            sems.at[slot])

    @pl.when(j == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        if agg == "min":
            out_ref[...] = jnp.full(out_ref.shape, jnp.inf, out_ref.dtype)
        elif agg == "max":
            out_ref[...] = jnp.full(out_ref.shape, -jnp.inf,
                                    out_ref.dtype)
        else:
            out_ref[...] = jnp.zeros_like(out_ref)
        if agg in ("var", "std"):
            mean_ref[...] = jnp.zeros_like(mean_ref)
            m2_ref[...] = jnp.zeros_like(m2_ref)
        dma(0, 0).start()

    slot = jax.lax.rem(j, 2)

    @pl.when(j + 1 < edge_steps)
    def _prefetch_next():
        dma(1 - slot, j + 1).start()

    dma(slot, j).wait()

    if upcast:
        tile_ref[...] = sbuf[slot].astype(jnp.float32)
    base = j * eb

    def body(e, _):
        d = dst_ref[base + e]
        dl = jnp.maximum(d, 0)
        ok = d >= 0
        if upcast:
            row = tile_ref[pl.ds(e, 1), :]
        else:
            row = sbuf[slot, pl.ds(e, 1), :]
        if agg in ("sum", "mean"):
            cur = out_ref[pl.ds(dl, 1), :]
            out_ref[pl.ds(dl, 1), :] = \
                jnp.where(ok, cur + row, cur)
        elif agg in ("min", "max"):
            cur = out_ref[pl.ds(dl, 1), :]
            upd = jnp.minimum(cur, row) if agg == "min" \
                else jnp.maximum(cur, row)
            out_ref[pl.ds(dl, 1), :] = jnp.where(ok, upd, cur)
        else:                               # Welford mean / M2
            c = cnt_ref[pl.ds(dl, 1), :]
            c_new = c + jnp.where(ok, 1.0, 0.0)
            mean = mean_ref[pl.ds(dl, 1), :]
            delta = row - mean
            mean_new = mean + jnp.where(
                ok, delta / jnp.maximum(c_new, 1.0), 0.0)
            m2 = m2_ref[pl.ds(dl, 1), :]
            mean_ref[pl.ds(dl, 1), :] = mean_new
            m2_ref[pl.ds(dl, 1), :] = \
                m2 + jnp.where(ok, delta * (row - mean_new), 0.0)
            cnt_ref[pl.ds(dl, 1), :] = c_new
        if agg in ("mean", "min", "max"):
            c = cnt_ref[pl.ds(dl, 1), :]
            cnt_ref[pl.ds(dl, 1), :] = c + jnp.where(ok, 1.0, 0.0)
        return 0

    jax.lax.fori_loop(0, eb, body, 0)

    @pl.when(j == edge_steps - 1)
    def _finalize():
        if agg == "mean":
            out_ref[...] = out_ref[...] / jnp.maximum(cnt_ref[...], 1.0)
        elif agg in ("min", "max"):
            o = out_ref[...]
            out_ref[...] = jnp.where(jnp.isfinite(o), o, 0.0)
        elif agg in ("var", "std"):
            var = m2_ref[...] / jnp.maximum(cnt_ref[...], 1.0)
            var = jnp.maximum(var, 1e-12)   # clamp: sqrt'(0)=inf -> NaNs
            out_ref[...] = jnp.sqrt(var) if agg == "std" else var


def segment_aggregate_v2_pallas(messages, seg_ids, num_segments: int, *,
                                agg: str = "sum", edge_block: int = 128,
                                node_block: int = 128,
                                interpret: bool | None = None):
    """One-hot-free segment aggregation (``gather_mode="dma"``, the
    default) — same contract as ``segment_aggregate_pallas`` (messages
    (E, F) at fp32/bf16/int8 storage width, fp32 accumulation, seg_ids
    (E,) with -1/out-of-range = padding, (num_segments, F) float32 out,
    empty segments zero-fill) — but a different machine: the dst stream
    rides in SMEM via scalar prefetch, message tiles are double-buffered
    HBM->VMEM by explicit async copies, and the whole accumulator table
    (plus the Welford mean/M2 pair for var/std) is VMEM-resident, so the
    edge stream is swept exactly once (``node_block`` is accepted for
    knob compatibility and ignored). F is zero-padded to a multiple of
    128 lanes inside and sliced back on the way out.

    Grid: (edge_tiles,). Scratch: two-slot (2, EB, F) message buffer at
    storage width + a DMA semaphore pair + the (num_segments, 1) count
    column + (num_segments, F) Welford mean/M2 for var/std + the fp32
    (EB, F) widened tile for bf16/int8 messages.
    """
    assert agg in AGGS, agg
    del node_block                       # v2 keeps the whole table
    e, f = messages.shape
    if e == 0 or num_segments == 0:
        return jnp.zeros((num_segments, f), jnp.float32)
    seg_ids = seg_ids.astype(jnp.int32)
    seg_ids = jnp.where((seg_ids >= 0) & (seg_ids < num_segments),
                        seg_ids, -1)
    eb = edge_tile(e, edge_block, messages.dtype)
    e_pad = (-e) % eb
    if e_pad:
        messages = jnp.pad(messages, ((0, e_pad), (0, 0)))
        seg_ids = jnp.pad(seg_ids, (0, e_pad), constant_values=-1)
    messages = pad_lanes(messages)
    fp = messages.shape[1]
    steps = (e + e_pad) // eb
    welford = agg in ("var", "std")
    track_count = agg != "sum"
    upcast = messages.dtype != jnp.float32
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),        # messages stay HBM
        ],
        out_specs=pl.BlockSpec((num_segments, fp), lambda j, d_r: (0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, eb, fp), messages.dtype),  # two-slot buffer
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((num_segments if track_count else 8, 1),
                       jnp.float32),
            pltpu.VMEM((num_segments if welford else 8, fp), jnp.float32),
            pltpu.VMEM((num_segments if welford else 8, fp), jnp.float32),
            pltpu.VMEM((eb if upcast else 8, fp), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_seg_v2_kernel, agg=agg, edge_steps=steps,
                          eb=eb, upcast=upcast),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_segments, fp), jnp.float32),
        compiler_params=compiler_params(),
        interpret=resolve_interpret(interpret),
    )(seg_ids, messages)
    return out[:, :f]
