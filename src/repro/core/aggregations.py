"""Single-pass partial aggregations (paper §V-B "Partial Aggregations").

GNNBuilder's FPGA kernels aggregate neighbor embeddings in O(1) space with
one pass over the (sorted) edge stream; variance/std use Welford's online
algorithm [37]. We implement the identical math twice:

* a *streaming* form (init / update / finalize) — consumed by the Pallas
  kernels (the padded-table ``gnn_aggregate`` and the packed-COO
  ``segment_aggregate``) and by the pure-scan reference, and
* a *segment* form over COO edge lists — the hot path for both padded
  graphs and the packed GraphBatch IR (DESIGN_BATCHING.md), dispatched
  through a backend switch: ``backend="xla"`` (default; jax.ops.segment_*
  lower to efficient sorted-segment reductions under pjit) or
  ``backend="pallas"`` (the fused ``kernels/segment_aggregate`` edge-block
  kernel, engaged on single-device serving via
  ``set_default_backend``/``--agg-backend``).

A third entry point, ``gather_aggregate``, fuses the *gather* stage into
the same dispatch: it takes the node-feature table plus the raw src/dst
edge-id streams (and an optional per-edge scale) instead of a
pre-gathered message tensor. Under ``backend="pallas"`` it lowers to
``kernels/fused_gather_aggregate`` and the (E, F) message tensor never
touches HBM — the paper's streamed gather->phi->aggregate pipeline;
under ``backend="xla"`` it materializes the messages with ``jnp.take``
and segment-reduces them (the safe pjit path, and the parity oracle).

Both entry points are *precision-polymorphic* (``precision=`` takes a
``quantization.LayerPrecision``): the node table / message tensor is
stored and streamed at the layer's compute width — bf16 tiles, or true
int8 tiles on the Pallas path (the per-tensor dequantization scale folds
into the kernels' existing per-edge scale path / finalize) — while
accumulation always runs in fp32 (exact int32-style sums for int8). The
XLA path mirrors the same numerics with fake-quant fp32 values, so the
two backends stay within fp32 tolerance of each other at every
precision (docs/KERNELS.md has the tolerance table).

Supported: sum, mean, min, max, var, std (matching the paper);
``gather_aggregate`` covers the sum/mean/min/max family that linear-phi
convs (GCN/SAGE/GIN) lower to.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

AGGREGATIONS = ("sum", "mean", "min", "max", "var", "std")

SEGMENT_BACKENDS = ("xla", "pallas")

# gather-stage kernel generations for ``gather_aggregate``'s Pallas path
# (dse.SPACE gather_mode): "dma" = the one-hot-free v2 kernel
# (scalar-prefetched ids + dynamic-slice gather), "onehot" = the legacy
# (N, EB) one-hot MXU contraction (docs/KERNELS.md)
GATHER_MODES = ("onehot", "dma")

# Process-wide defaults for ``segment_aggregate``'s backend=/tile
# arguments. "xla" everywhere a program may run under pjit; serving flips
# to "pallas" on single-device hosts (launch/serve.py --agg-backend).
# Tile sizes are the DSE knobs (dse.SPACE edge_block/node_block).
_DEFAULT_BACKEND = "xla"
_DEFAULT_EDGE_BLOCK = 128
_DEFAULT_NODE_BLOCK = 128
_DEFAULT_GATHER_MODE = "dma"
# None = auto: interpret the Pallas kernel everywhere except a real TPU
# backend (Mosaic compiles only there; interpret mode is the CPU/CI path)
_DEFAULT_INTERPRET: bool | None = None


def resolve_interpret(interpret: bool | None) -> bool:
    """The one place a Pallas kernel's ``interpret`` flag is decided:
    an explicit bool wins, then the process default
    (``set_default_backend(interpret=...)``), then the backend — Mosaic
    compiles on a TPU, and everything else interprets."""
    if interpret is None:
        interpret = _DEFAULT_INTERPRET
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return interpret


def set_default_backend(backend: str, edge_block: int | None = None,
                        node_block: int | None = None,
                        interpret: bool | None = None,
                        gather_mode: str | None = None) -> str:
    """Set the process default segment-aggregation backend (and
    optionally the Pallas tile sizes / interpret mode / gather kernel
    generation); returns the previous backend so callers can restore it.
    Trace-time effective: jitted programs bake in whichever defaults
    were set when first traced."""
    global _DEFAULT_BACKEND, _DEFAULT_EDGE_BLOCK, _DEFAULT_NODE_BLOCK, \
        _DEFAULT_INTERPRET, _DEFAULT_GATHER_MODE
    # validate everything before mutating anything: a rejected call must
    # leave the process defaults untouched (no half-applied state)
    if backend not in SEGMENT_BACKENDS:
        raise ValueError(backend)
    if gather_mode is not None and gather_mode not in GATHER_MODES:
        raise ValueError(gather_mode)
    prev = _DEFAULT_BACKEND
    _DEFAULT_BACKEND = backend
    if edge_block is not None:
        _DEFAULT_EDGE_BLOCK = int(edge_block)
    if node_block is not None:
        _DEFAULT_NODE_BLOCK = int(node_block)
    if interpret is not None:
        _DEFAULT_INTERPRET = bool(interpret)
    if gather_mode is not None:
        _DEFAULT_GATHER_MODE = gather_mode
    return prev


def default_backend() -> str:
    return _DEFAULT_BACKEND


@contextlib.contextmanager
def backend_scope(backend: str, edge_block: int | None = None,
                  node_block: int | None = None,
                  interpret: bool | None = None,
                  gather_mode: str | None = None):
    """Temporarily override the segment-aggregation defaults. Wrap the
    *tracing* of a jitted program (e.g. Project.gen_hw_model's infer fns)
    to bake a backend + tile choice into that program only."""
    global _DEFAULT_BACKEND, _DEFAULT_EDGE_BLOCK, _DEFAULT_NODE_BLOCK, \
        _DEFAULT_INTERPRET, _DEFAULT_GATHER_MODE
    prev = (_DEFAULT_BACKEND, _DEFAULT_EDGE_BLOCK, _DEFAULT_NODE_BLOCK,
            _DEFAULT_INTERPRET, _DEFAULT_GATHER_MODE)
    try:
        set_default_backend(backend, edge_block, node_block, interpret,
                            gather_mode)
        yield
    finally:
        (_DEFAULT_BACKEND, _DEFAULT_EDGE_BLOCK, _DEFAULT_NODE_BLOCK,
         _DEFAULT_INTERPRET, _DEFAULT_GATHER_MODE) = prev


# ------------------------------------------------------- streaming form --
def init_state(agg: str, dim: int, dtype=jnp.float32) -> dict:
    z = jnp.zeros((dim,), dtype)
    if agg == "sum" or agg == "mean":
        return {"acc": z, "count": jnp.zeros((), dtype)}
    if agg == "min":
        return {"acc": jnp.full((dim,), jnp.inf, dtype)}
    if agg == "max":
        return {"acc": jnp.full((dim,), -jnp.inf, dtype)}
    if agg in ("var", "std"):  # Welford: mean, M2, count
        return {"mean": z, "m2": z, "count": jnp.zeros((), dtype)}
    raise ValueError(agg)


def update(agg: str, state: dict, x) -> dict:
    """One neighbor embedding x: (dim,). O(1) space."""
    if agg in ("sum", "mean"):
        return {"acc": state["acc"] + x, "count": state["count"] + 1}
    if agg == "min":
        return {"acc": jnp.minimum(state["acc"], x)}
    if agg == "max":
        return {"acc": jnp.maximum(state["acc"], x)}
    if agg in ("var", "std"):
        c = state["count"] + 1
        delta = x - state["mean"]
        mean = state["mean"] + delta / c
        m2 = state["m2"] + delta * (x - mean)
        return {"mean": mean, "m2": m2, "count": c}
    raise ValueError(agg)


def finalize(agg: str, state: dict):
    if agg == "sum":
        return state["acc"]
    if agg == "mean":
        return state["acc"] / jnp.maximum(state["count"], 1.0)
    if agg in ("min", "max"):
        # isolated nodes: neutral element -> 0 (paper zero-fills)
        return jnp.where(jnp.isfinite(state["acc"]), state["acc"], 0.0)
    if agg in ("var", "std"):
        var = state["m2"] / jnp.maximum(state["count"], 1.0)
        var = jnp.maximum(var, 1e-12)   # clamp: sqrt'(0) = inf -> NaN grads
        return jnp.sqrt(var) if agg == "std" else var
    raise ValueError(agg)


def aggregate_stream(agg: str, xs, mask=None):
    """Reference streaming aggregation over xs: (n, dim) via lax.scan."""
    n, dim = xs.shape
    if mask is None:
        mask = jnp.ones((n,), bool)

    def step(state, inp):
        x, m = inp
        new = update(agg, state, x.astype(jnp.float32))
        state = jax.tree_util.tree_map(
            lambda a, b: jnp.where(m, b, a), state, new)
        return state, None

    state, _ = jax.lax.scan(step, init_state(agg, dim), (xs, mask))
    return finalize(agg, state)


def _active_precision(precision):
    """None for the fp32 fast path, the LayerPrecision otherwise."""
    if precision is None or precision.compute == "fp32":
        return None
    return precision


# --------------------------------------------------------- segment form --
def segment_aggregate(agg: str, messages, seg_ids, num_segments: int,
                      valid=None, *, backend: str | None = None,
                      edge_block: int | None = None,
                      node_block: int | None = None,
                      interpret: bool | None = None,
                      precision=None, gather_mode: str | None = None):
    """messages: (E, dim) -> (num_segments, dim). seg_ids: (E,) int32;
    padded edges carry seg_ids == num_segments (dropped).

    backend=None uses the process default (``set_default_backend``);
    "pallas" routes through the fused edge-block kernel with the given
    tile sizes (DSE knobs ``edge_block``/``node_block``), "xla" through
    jax.ops.segment_*. Both produce identical results to fp32 tolerance;
    the Pallas path is forward-only (no custom VJP yet).

    gather_mode=None uses the process default ("dma"): the one-hot-free
    v2 schedule — scalar-prefetched dst stream, double-buffered message
    DMA, whole-table VMEM accumulators, one sweep over the edge stream
    (this is the schedule PNA towers and var/std ride). "onehot" keeps
    the legacy (NB, EB) destination one-hot (GATHER_MODES; the DSE
    featurizes the choice).

    precision (a ``quantization.LayerPrecision``) sets the *storage*
    width of the message tensor: bf16 tiles, or — on the Pallas path —
    true int8 tiles quantized onto the layer's activation grid with the
    per-tensor dequant scale applied on the fp32 accumulator output
    (var scales by s^2, std by s, the linear family by s). The XLA path
    runs the same grids as fake-quant fp32. Accumulation is fp32 at
    every precision."""
    backend = backend or _DEFAULT_BACKEND
    if backend not in SEGMENT_BACKENDS:
        raise ValueError(backend)
    lp = _active_precision(precision)
    if lp is not None and lp.compute == "bf16":
        messages = messages.astype(jnp.bfloat16)
    if backend == "pallas":
        from repro.core import quantization as Q
        from repro.kernels.segment_aggregate.ops import (
            segment_aggregate as _pallas_segment_aggregate)
        dequant = None
        if lp is not None and lp.compute == "int8":
            messages = Q.quantize_int8(messages, lp.act_fpx)
            s = lp.act_fpx.resolution
            dequant = s * s if agg == "var" else s
        out = _pallas_segment_aggregate(
            messages, seg_ids, valid, num_segments=num_segments, agg=agg,
            edge_block=edge_block or _DEFAULT_EDGE_BLOCK,
            node_block=node_block or _DEFAULT_NODE_BLOCK,
            interpret=resolve_interpret(interpret),
            gather_mode=gather_mode or _DEFAULT_GATHER_MODE)
        return out if dequant is None else out * dequant
    if lp is not None and lp.compute == "int8":
        from repro.core import quantization as Q
        messages = Q.quantize(messages, lp.act_fpx)   # fake-quant mirror
    if valid is not None:
        seg_ids = jnp.where(valid, seg_ids, num_segments)
    m = messages.astype(jnp.float32)
    ns = num_segments + 1           # +1 bucket swallows padding
    if agg == "sum":
        out = jax.ops.segment_sum(m, seg_ids, ns)
    elif agg == "mean":
        s = jax.ops.segment_sum(m, seg_ids, ns)
        c = jax.ops.segment_sum(jnp.ones_like(m[:, :1]), seg_ids, ns)
        out = s / jnp.maximum(c, 1.0)
    elif agg == "min":
        out = jax.ops.segment_min(m, seg_ids, ns)
        out = jnp.where(jnp.isfinite(out), out, 0.0)
    elif agg == "max":
        out = jax.ops.segment_max(m, seg_ids, ns)
        out = jnp.where(jnp.isfinite(out), out, 0.0)
    elif agg in ("var", "std"):
        # two-pass shifted form: E[(x - mu)^2] matches the Welford kernel
        # to fp32 tolerance (E[x^2] - E[x]^2 loses near-duplicate
        # segments to catastrophic cancellation)
        s = jax.ops.segment_sum(m, seg_ids, ns)
        c = jnp.maximum(jax.ops.segment_sum(
            jnp.ones_like(m[:, :1]), seg_ids, ns), 1.0)
        mu = s / c
        dev = m - jnp.take(mu, seg_ids, axis=0)
        var = jax.ops.segment_sum(jnp.square(dev), seg_ids, ns) / c
        var = jnp.maximum(var, 1e-12)
        out = jnp.sqrt(var) if agg == "std" else var
    else:
        raise ValueError(agg)
    return out[:num_segments]


def segment_softmax(logits, seg_ids, num_segments: int, valid=None, *,
                    backend: str | None = None,
                    edge_block: int | None = None,
                    interpret: bool | None = None):
    """Per-edge softmax weights normalized within each destination
    segment — the attention-conv reduction (GAT). logits: (E,) ->
    (E,) float32; seg_ids: (E,) int32 with padding marked by -1, any id
    >= num_segments, or ``valid == False``.

    Numerically stable at any logit magnitude: both backends subtract
    the per-segment max before exponentiating (the Pallas path is the
    online-softmax machine of ``kernels/segment_softmax``; the XLA path
    is segment_max + shifted exp + segment_sum), so +-1e4 logits never
    overflow. A -inf logit on a valid edge is a masked attention slot:
    it contributes 0 to the denominator and gets weight 0; an all-masked
    or empty segment yields all-zero weights — never NaN/Inf.

    Attention weights are *not* precision-polymorphic: the logit/softmax
    math always runs fp32 regardless of the layer's PrecisionPolicy
    (the documented int8 exclusion — only the projection and the
    aggregate message stream quantize; docs/KERNELS.md)."""
    backend = backend or _DEFAULT_BACKEND
    if backend not in SEGMENT_BACKENDS:
        raise ValueError(backend)
    if backend == "pallas":
        from repro.kernels.segment_softmax.ops import (
            segment_softmax as _pallas_segment_softmax)
        return _pallas_segment_softmax(
            logits, seg_ids, valid, num_segments=num_segments,
            edge_block=edge_block or _DEFAULT_EDGE_BLOCK,
            interpret=resolve_interpret(interpret))
    seg_ids = jnp.asarray(seg_ids, jnp.int32)
    ok = (seg_ids >= 0) & (seg_ids < num_segments)
    if valid is not None:
        ok = ok & valid
    seg = jnp.where(ok, seg_ids, num_segments)
    ns = num_segments + 1           # +1 bucket swallows padding
    z = jnp.asarray(logits, jnp.float32)
    m = jax.ops.segment_max(z, seg, ns)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    # mask before the exp: a padding logit can exceed its (overflow)
    # bucket statistics and overflow to +inf on lanes where() discards
    p = jnp.where(ok, jnp.exp(jnp.where(ok, z, -jnp.inf)
                              - jnp.take(m_safe, seg)), 0.0)
    denom = jax.ops.segment_sum(p, seg, ns)
    return p / jnp.maximum(jnp.take(denom, seg), 1e-30)


GATHER_AGGREGATIONS = ("sum", "mean", "min", "max")


def gather_aggregate(agg: str, x, src, dst, num_segments: int, valid=None,
                     scale=None, *, backend: str | None = None,
                     edge_block: int | None = None,
                     node_block: int | None = None,
                     interpret: bool | None = None,
                     precision=None, gather_mode: str | None = None):
    """Fused gather -> phi -> aggregate over packed COO id streams.

    x: (N, F) node features; src/dst: (E,) int32 endpoint ids (padding:
    -1, out-of-range, or ``valid == False``); scale: optional (E,)
    per-edge message scale applied before aggregation (the GCN symmetric
    norm). Returns (num_segments, F) float32.

    backend=None uses the process default. "pallas" routes through the
    fused edge-block kernel for sum/mean/min/max — the (E, F) message
    tensor is never materialized; var/std fall back to the materialized
    gather + the Pallas segment kernel. "xla" always materializes
    ``jnp.take(x, src)`` and segment-reduces it — the materialized
    baseline the fused kernel is numerics-pinned against.

    gather_mode=None uses the process default ("dma"): the one-hot-free
    v2 kernel — scalar-prefetched id streams, per-edge dynamic-slice
    gather, double-buffered scale copies. "onehot" keeps the legacy
    (N, EB) one-hot MXU contraction (GATHER_MODES; the DSE featurizes
    the choice).

    precision (a ``quantization.LayerPrecision``) sets the storage width
    of the node table: bf16 tiles, or — on the fused Pallas path — true
    int8 tiles whose per-tensor dequant scale is *folded into the
    existing per-edge scale stream* (phi costs nothing extra; the fold is
    exact for the whole sum/mean/min/max family since the scale is a
    positive per-tensor constant). The XLA path mirrors the same grid as
    fake-quant fp32; accumulation is fp32 everywhere."""
    backend = backend or _DEFAULT_BACKEND
    if backend not in SEGMENT_BACKENDS:
        raise ValueError(backend)
    lp = _active_precision(precision)
    if lp is not None and lp.compute == "bf16":
        x = x.astype(jnp.bfloat16)
    if backend == "pallas" and agg in GATHER_AGGREGATIONS:
        from repro.kernels.fused_gather_aggregate.ops import (
            fused_gather_aggregate as _pallas_gather_aggregate)
        if lp is not None and lp.compute == "int8":
            from repro.core import quantization as Q
            s = lp.act_fpx.resolution
            x = Q.quantize_int8(x, lp.act_fpx)
            scale = jnp.full(jnp.asarray(src).shape, s, jnp.float32) \
                if scale is None else scale.astype(jnp.float32) * s
        return _pallas_gather_aggregate(
            x, src, dst, valid, scale, num_segments=num_segments, agg=agg,
            edge_block=edge_block or _DEFAULT_EDGE_BLOCK,
            node_block=node_block or _DEFAULT_NODE_BLOCK,
            interpret=resolve_interpret(interpret),
            gather_mode=gather_mode or _DEFAULT_GATHER_MODE)
    if lp is not None and lp.compute == "int8":
        from repro.core import quantization as Q
        x = Q.quantize(x, lp.act_fpx)                 # fake-quant mirror
    # materialized path: gather the (E, F) message tensor, then reduce.
    # Out-of-range ids on *either* stream are padding (same contract as
    # the fused kernel): clamp before the take so no fill-value NaNs can
    # leak, and drop the edge via the validity mask. The gathered
    # messages keep x's storage dtype until the scale/accumulate stage.
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    msg = jnp.take(x, jnp.clip(src, 0, x.shape[0] - 1), axis=0)
    if scale is not None:
        msg = msg.astype(jnp.float32) * scale[:, None]
    ok = (src >= 0) & (src < x.shape[0]) \
        & (dst >= 0) & (dst < num_segments)
    if valid is not None:
        ok = ok & valid
    # the fused family quantizes the *table* (above) so the pallas and
    # XLA traces see identical messages; the non-fused aggregations
    # (var/std) share this materialized path on both backends, so the
    # precision forwards to the segment stage and the message tensor
    # itself streams at storage width through the segment kernel
    inner_lp = lp if agg not in GATHER_AGGREGATIONS else None
    return segment_aggregate(agg, msg, dst, num_segments, ok,
                             backend=backend, edge_block=edge_block,
                             node_block=node_block, interpret=interpret,
                             precision=inner_lp, gather_mode=gather_mode)


def segment_counts(seg_ids, num_segments: int, valid=None):
    """Per-segment element counts: (E,) int ids -> (num_segments,) float.

    With packed GraphBatch buffers this yields per-graph node or edge
    counts (pass node_graph_id / edge_graph_id); padding slots carry
    seg_ids == num_segments and fall into the dropped overflow bucket.
    """
    seg_ids = jnp.asarray(seg_ids)
    if valid is not None:
        seg_ids = jnp.where(valid, seg_ids, num_segments)
    ones = jnp.ones(seg_ids.shape, jnp.float32)
    return jax.ops.segment_sum(ones, seg_ids, num_segments + 1)[
        :num_segments]


def degrees(edge_index, num_nodes: int, valid=None):
    """(in_degree, out_degree) from padded COO (E, 2) with -1 padding."""
    src, dst = edge_index[:, 0], edge_index[:, 1]
    if valid is None:
        valid = src >= 0
    ones = valid.astype(jnp.float32)
    indeg = jax.ops.segment_sum(
        ones, jnp.where(valid, dst, num_nodes), num_nodes + 1)[:num_nodes]
    outdeg = jax.ops.segment_sum(
        ones, jnp.where(valid, src, num_nodes), num_nodes + 1)[:num_nodes]
    return indeg, outdeg
