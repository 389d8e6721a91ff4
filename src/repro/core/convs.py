"""Message-passing graph convolutions (paper §V-A, Fig. 3).

Every conv follows the explicit gather -> phi -> aggregate -> gamma
dataflow over padded COO graphs, which is what lets GNNBuilder support
anisotropic layers (PNA) that SpMM accelerators cannot express.

Kernels: GCN [23], GraphSAGE [24], GIN(E) [26], PNA [27] — the paper's
Table II set — plus GAT [25], the attention conv the GNN-acceleration
survey names as the standard coverage axis (per-edge softmax is a new
reduction shape: ``kernels/segment_softmax``), and GatedGCN
(arXiv:1711.07553), the local conv of GraphGPS, which carries an edge
state from layer to layer (``ConvSpec.edge_state``). Each provides
``plan(cfg)`` + ``apply(params, g, x)``, where ``g`` is a dict
{edge_index (E,2), edge_feat (E,Fe), num_nodes, in_deg, out_deg,
valid_e} with static max shapes (MAX_NODES/MAX_EDGES analogue).

Convs are *registered*, not hard-wired: ``register_conv`` records each
conv's (plan, apply) pair and capability flags in ``CONV_REGISTRY``
(``ConvSpec``), and everything downstream — the dataflow planner, the
residency rule, ``dse.SPACE["conv"]``, the perf-model conv one-hots,
and the test parity grids — enumerates convs from the registry. The
legacy ``CONV_TYPES`` / ``REORDERABLE_CONVS`` / ``RESIDENT_CONVS``
tuples survive as registry-derived views, beside ``STACK_CONVS``, the
convs a plain conv stack runs.

The same applies serve both execution formats: a single padded graph and
a packed GraphBatch (many graphs in one flat buffer). A packed batch is
just the disjoint union graph — edge_index holds *global* node ids, so
message passing never crosses graph boundaries and the segment reductions
drop padding edges (src == -1) via ``valid_e``.

Linear-phi convs (GCN/SAGE) additionally carry a *dataflow* choice —
transform-then-aggregate vs aggregate-then-transform. Because their phi
commutes with the (linear) aggregation, either order is exact, but the
edge stream moves ``F_agg``-wide messages, so aggregating at
``min(F_in, F_out)`` width cuts both per-edge bandwidth and matmul
traffic (the aggregate-vs-transform reordering of the GNN-acceleration
survey). ``resolve_dataflow`` picks the cheaper order from a closed-form
cost model over (in_dim, out_dim, avg_degree); ``dataflow="auto"`` can be
overridden per layer stack via ``ConvConfig.dataflow`` /
``GNNModelConfig.gnn_dataflow`` / ``Project(dataflow=...)``.

Every conv also carries a per-layer precision (``ConvConfig.precision``,
a ``quantization.LayerPrecision`` resolved by the model-level
``PrecisionPolicy``): the tensor entering the edge stream is stored and
streamed at the layer's compute width (bf16 / int8 tiles through the
precision-polymorphic aggregation dispatch), while accumulation — and
the model's residual stream — stay fp32. The byte width also enters the
dataflow cost model: the edge-stream term of ``dataflow_cost`` scales
with bytes-per-value, so low-precision layers shrink exactly the term
the reordering optimizes.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import aggregations as agg_mod
from repro.core.quantization import LayerPrecision
from repro.nn.layers import act, linear, linear_plan
from repro.nn.param import ParamSpec

PNA_AGGS = ("mean", "min", "max", "std")
PNA_SCALERS = ("identity", "amplification", "attenuation")

DATAFLOWS = ("auto", "aggregate_first", "transform_first")

PRECISION_GRID = ("fp32", "bf16", "int8")


# ------------------------------------------------------- conv registry --
@dataclasses.dataclass(frozen=True)
class ConvSpec:
    """One conv's capability contract — the single place the planner,
    DSE, perf model, and the parity grids enumerate convs from
    (docs/KERNELS.md, docs/DSE.md). Adding a conv is one
    ``register_conv`` call next to its plan/apply pair; nothing else in
    the stack hard-codes conv names."""
    name: str
    plan: object          # (ConvConfig, dtype) -> param plan
    apply: object         # (params, g, x, ConvConfig) -> (N, F_out)
    # phi is a plain linear map: aggregation commutes with the
    # transform, so the dataflow planner may reorder the layer
    reorderable: bool = False
    # the multi-layer VMEM-residency kernel can execute it (linear phi +
    # a single scalar per edge); must stay in sync with
    # kernels.fused_gather_aggregate.residency.RESIDENT_KINDS
    resident: bool = False
    # carries a per-edge logit/softmax stage (segment_softmax): adds the
    # attention term to dataflow_cost and excludes the logit math from
    # int8 (the weights themselves stay fp32 at every policy)
    attention: bool = False
    # PrecisionPolicy grid the conv's datapath supports (the parity
    # harness precision axis); attention convs still list int8 — only
    # their projection/aggregate stream quantizes, never the softmax
    precisions: tuple = PRECISION_GRID
    # partitioned-vs-padded-oracle parity holds *bitwise* at fp32: the
    # conv's per-segment reductions preserve the edge stream's relative
    # order on every device (the serve-path acceptance contract)
    partition_bitwise: bool = False
    # enumerated in dse.SPACE["conv"] / perf-model conv one-hots
    dse: bool = True
    # takes an edge state (``g["edge_state"]``, the raw ``edge_feat`` when
    # absent) and returns ``(node_out, edge_out)``; ``ConvConfig.edge_dim``
    # is the width of the state it takes. Only a block that defines the
    # state's update runs it (GraphGPS, ``core/gps.py``); a plain conv
    # stack refuses it, so it is not in ``STACK_CONVS``
    edge_state: bool = False


CONV_REGISTRY: dict[str, ConvSpec] = {}
_REGISTRY_LISTENERS: list = []

# registry-derived capability tuples, rebuilt by every (un)register call
# — read these as ``convs.CONV_TYPES`` (attribute access), not via
# ``from ... import`` snapshots, so late registrations stay visible
CONV_TYPES: tuple = ()
REORDERABLE_CONVS: tuple = ()
RESIDENT_CONVS: tuple = ()
STACK_CONVS: tuple = ()


def _registry_changed():
    global CONV_TYPES, REORDERABLE_CONVS, RESIDENT_CONVS, STACK_CONVS
    CONV_TYPES = tuple(CONV_REGISTRY)
    STACK_CONVS = tuple(n for n, s in CONV_REGISTRY.items()
                        if not s.edge_state)
    REORDERABLE_CONVS = tuple(n for n, s in CONV_REGISTRY.items()
                              if s.reorderable)
    RESIDENT_CONVS = tuple(n for n, s in CONV_REGISTRY.items()
                           if s.resident)
    for fn in list(_REGISTRY_LISTENERS):
        fn()


def register_conv(name: str, plan, apply, **caps) -> ConvSpec:
    """Register a conv's (plan, apply) pair plus capability flags
    (``ConvSpec`` fields). Derived enumerations — ``CONV_TYPES``,
    ``dse.SPACE["conv"]``, ``perf_model.FEATURE_NAMES`` conv one-hots,
    the parity-grid axes — rebuild immediately."""
    spec = ConvSpec(name=name, plan=plan, apply=apply, **caps)
    CONV_REGISTRY[name] = spec
    _registry_changed()
    return spec


def unregister_conv(name: str) -> None:
    del CONV_REGISTRY[name]
    _registry_changed()


def conv_spec(name: str) -> ConvSpec:
    try:
        return CONV_REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown conv {name!r}; registered: "
                         f"{CONV_TYPES}") from None


def on_registry_change(fn) -> None:
    """Subscribe to registry mutations (dse / perf_model derive their
    conv axes through this). The callback takes no arguments and runs
    synchronously inside every (un)register call."""
    _REGISTRY_LISTENERS.append(fn)

# word-equivalence factor between the two cost-model currencies: at the
# TPUTarget roofline (819 GB/s HBM, 197 TFLOP/s) one fp32 word moved
# costs the same time as ~480 MACs, so compute terms divide by this to
# land in the same per-node units as the streaming term
_MACS_PER_WORD = 480.0


@dataclasses.dataclass(frozen=True)
class ConvConfig:
    in_dim: int
    out_dim: int
    edge_dim: int = 0
    conv: str = "gcn"
    activation: str = "relu"
    # hardware parallelism factors (paper p_in/p_out -> kernel tile sizes)
    p_in: int = 1
    p_out: int = 1
    delta: float = 1.0        # PNA log-degree normalizer (avg log degree)
    # transform/aggregate ordering for linear convs (resolve_dataflow)
    dataflow: str = "auto"
    avg_degree: float = 2.0   # dataset statistic driving the cost model
    # per-layer datapath precision (PrecisionPolicy.layer(i)); the
    # default is the fp32 identity
    precision: LayerPrecision = LayerPrecision()


def gather_compute_flops(num_nodes: int, num_edges: int, feat_dim: int,
                         gather_mode: str = "dma",
                         node_block: int = 128) -> float:
    """Modeled FLOPs the gather+aggregate stage itself spends on one
    layer's edge sweep — the term the pre-v2 cost model omitted (it
    counted bytes only, which made the legacy one-hot kernel "win" on
    paper while losing 40x on the clock).

    "onehot": every (node_tile, edge_tile) grid step builds and
    contracts dense one-hots — ``2 * EB * F * (N + NB)`` MACs-as-FLOPs
    per step — so the sweep costs
    ``2 * E * F * (N + node_block) * ceil(N / node_block)``; at realistic
    N this is compute-bound by orders of magnitude. "dma" gathers each
    row by dynamic slice: ~3 FLOPs per message element (scale multiply +
    accumulate + count), linear in ``E * F``. The materialized XLA path
    has the same ~3 E F compute shape; it pays in message-tensor HBM
    bytes instead (see benchmarks/fused_gather.py)."""
    if gather_mode == "onehot":
        node_tiles = -(-num_nodes // node_block)
        return 2.0 * num_edges * feat_dim * (num_nodes + node_block) \
            * node_tiles
    if gather_mode == "dma":
        return 3.0 * num_edges * feat_dim
    raise ValueError(gather_mode)


def dataflow_cost(in_dim: int, out_dim: int, avg_degree: float,
                  msg_bytes: float = 4.0, gather_mode: str = "dma",
                  num_nodes: int = 1024, node_block: int = 128,
                  attention: bool = False) -> dict:
    """Per-node cost (fp32-word-equivalents moved through the edge
    pipeline + MACs/F) of each ordering. The W matmul costs
    ``in_dim * out_dim`` MACs per node either way; the edge stream
    carries ``avg_degree`` messages per node at the aggregation width —
    F_in when aggregating first, F_out when transforming first — and at
    the layer's storage width: ``msg_bytes`` (the PrecisionPolicy byte
    width, 4 = fp32) scales the streaming term, so low-precision layers
    shrink exactly what the reordering optimizes. The degree scales how
    much the reordering matters; the sign of the difference is
    ``out_dim - in_dim``.

    The gather stage's own compute (``gather_compute_flops``) rides on
    the same per-message-element axis, converted to word-equivalents via
    the roofline ratio ``_MACS_PER_WORD``: negligible for "dma"
    (~0.003 words/element — the v2 kernel is bandwidth-bound), dominant
    for "onehot" (its dense contractions grow with ``num_nodes``), so
    ordering decisions stay honest under either kernel generation.

    ``attention`` adds the logit/softmax term of attention convs
    (registry ``ConvSpec.attention``): per in-edge, one fp32 logit read
    plus one fp32 weight write (the softmax weights never quantize, so
    this term does *not* scale with ``msg_bytes``) and the online-softmax
    arithmetic (~8 flops/edge: max, two exps, multiply-accumulate,
    divide). Width-independent, so it shifts both orderings equally —
    attention convs are not reorderable anyway (the softmax pins the
    aggregation to the projected width) — but it keeps the roofline and
    the DSE's modeled latency honest about what a gat layer streams."""
    matmul = in_dim * out_dim
    gflops = gather_compute_flops(num_nodes, avg_degree, 1.0,
                                  gather_mode, node_block)
    stream = avg_degree * (msg_bytes / 4.0) + gflops / 2.0 / _MACS_PER_WORD
    attn = avg_degree * (2.0 + 8.0 / 2.0 / _MACS_PER_WORD) \
        if attention else 0.0
    return {"aggregate_first": stream * in_dim + matmul + attn,
            "transform_first": stream * out_dim + matmul + attn}


def halo_comm_bytes(cut_edges: float, feat_dim: int,
                    bytes_per_value: float, num_layers: int) -> float:
    """Modeled inter-device traffic of intra-graph partitioned inference
    (pipeline.partition_graph): every message-passing boundary except the
    last exchanges the boundary-node rows the cut edges read, one feature
    row per cut edge at the layer's storage width. This is the comm-cost
    term the DSE ``partition`` axis is priced with — the same formula
    ``GraphPartition.comm_bytes`` reports for a concrete cut, here fed
    with a modeled cut so the fitted models can featurize designs that
    were never partitioned."""
    return float(cut_edges) * feat_dim * bytes_per_value \
        * max(num_layers - 1, 0)


def resolve_dataflow(cfg: ConvConfig) -> str:
    """Planner: the concrete ordering this conv layer executes with."""
    if cfg.dataflow not in DATAFLOWS:
        raise ValueError(cfg.dataflow)
    if cfg.conv not in REORDERABLE_CONVS:
        return "aggregate_first"
    if cfg.dataflow != "auto":
        return cfg.dataflow
    cost = dataflow_cost(cfg.in_dim, cfg.out_dim, cfg.avg_degree,
                         cfg.precision.bytes_per_value,
                         attention=conv_spec(cfg.conv).attention)
    return "transform_first" \
        if cost["transform_first"] < cost["aggregate_first"] \
        else "aggregate_first"


@dataclasses.dataclass(frozen=True)
class ResidencyPlan:
    """Planner verdict for the multi-layer VMEM-resident conv stack
    (kernels.fused_gather_aggregate.residency): whether keeping the node
    table on-chip across ``depth`` consecutive layers fits the VMEM
    budget, and the footprint arithmetic behind the decision. Recorded
    verbatim in Project config.json so a generated accelerator documents
    why residency did (not) engage."""
    legal: bool
    depth: int            # layers fused per kernel launch (min(req, L))
    fmax: int             # padded table width (lane-aligned max dim)
    vmem_required: int    # bytes at the widest point of the fused group
    vmem_budget: int      # bytes the kernel may use (its vmem_limit_bytes)
    reason: str


def residency_plan(layer_dims, node_budget: int, conv: str,
                   fusion_depth: int, *, quantized: bool = False,
                   vmem_bytes: int | None = None) -> ResidencyPlan:
    """VMEM-budget rule deciding when multi-layer residency is legal.

    layer_dims: [(in_dim, out_dim), ...] for the conv stack;
    node_budget: packed-batch node-table rows; quantized: a non-fp32
    policy adds the quantized shadow table. The working set is counted
    in Mosaic's layout: the pipelined (N, Fmax) input and output blocks
    are double-buffered, the aggregate accumulator (and the shadow when
    quantized) are single scratch tables, every (N, 1) column (self
    scale and node mask, double-buffered, plus the mean count) occupies
    a full 128-lane row per node, and the per-layer weight and bias
    blocks are double-buffered. ``fmax`` is the lane-aligned max layer
    width. Legal only for ``RESIDENT_CONVS`` (linear phi, one scalar per
    edge) at ``fusion_depth > 1``, and only when the working set fits
    ``vmem_bytes`` — by default ``kernels.tpu.VMEM_LIMIT_BYTES``, the
    scoped-VMEM limit the kernel compiles with, so a legal plan is one
    Mosaic accepts (tests/test_tpu_compile.py compiles the largest
    legal stack). The edge streams ride SMEM and do not enter the
    count."""
    from repro.kernels.tpu import LANES, VMEM_LIMIT_BYTES
    budget = int(VMEM_LIMIT_BYTES if vmem_bytes is None else vmem_bytes)
    depth = max(1, min(int(fusion_depth), len(layer_dims)))
    fmax = max(max(d) for d in layer_dims)
    fmax = -(-fmax // LANES) * LANES
    tables = 2 + 2 + 1 + (1 if quantized else 0)   # x0, out, aggr[, xq]
    columns = 2 + 2 + 1                         # self scale, mask, count
    required = (tables * node_budget * fmax * 4
                + columns * node_budget * LANES * 4
                + 2 * (3 * fmax * fmax + 8 * fmax) * 4)  # weights, bias
    if conv not in RESIDENT_CONVS:
        return ResidencyPlan(False, depth, fmax, required, budget,
                             f"conv {conv!r} not in {RESIDENT_CONVS}")
    if depth < 2:
        return ResidencyPlan(False, depth, fmax, required, budget,
                             "fusion_depth < 2: nothing to keep resident")
    if required > budget:
        return ResidencyPlan(False, depth, fmax, required, budget,
                             f"working set {required} B exceeds "
                             f"{budget} B VMEM budget")
    return ResidencyPlan(True, depth, fmax, required, budget,
                         f"{required} B fits {budget} B VMEM budget")


def _gather(x, idx):
    return jnp.take(x, jnp.maximum(idx, 0), axis=0)


def edge_endpoints(g):
    """(src, dst) columns of the padded COO edge buffer; -1 on padding."""
    return g["edge_index"][:, 0], g["edge_index"][:, 1]


def gcn_normalization(edge_index, in_deg, valid=None):
    """Precompute the GCN symmetric-norm scales from static graph fields:
    per-edge ``1/sqrt(d_u d_v)`` and per-node self-loop ``1/d_v``
    (degrees include the self loop). Hoisted out of ``gcn_apply`` so a
    layer stack computes it once per batch — ``graph_inputs`` /
    ``packed_inputs`` stash the result on ``g`` as ``gcn_edge_scale`` /
    ``gcn_self_scale``, shared by the fused and materialized paths."""
    src, dst = edge_index[:, 0], edge_index[:, 1]
    if valid is None:
        valid = src >= 0
    inv = jax.lax.rsqrt(jnp.maximum(in_deg + 1.0, 1e-12))
    edge_scale = _gather(inv, src) * _gather(inv, dst)
    edge_scale = jnp.where(valid, edge_scale, 0.0)
    return edge_scale, inv * inv


def _gcn_scales(g):
    es, ss = g.get("gcn_edge_scale"), g.get("gcn_self_scale")
    if es is None or ss is None:    # direct conv_apply callers
        es, ss = gcn_normalization(g["edge_index"], g["in_deg"],
                                   g.get("valid_e"))
    return es, ss


# ------------------------------------------------------------------ GCN --
def gcn_plan(cfg: ConvConfig, dtype=jnp.float32):
    return {"w": linear_plan(cfg.in_dim, cfg.out_dim, in_axis="embed",
                             out_axis="mlp", bias=True, dtype=dtype)}


def gcn_apply(params, g, x, cfg: ConvConfig):
    """x' = W (sum_u x_u / sqrt(d_u d_v)) + b  (self loops included).

    The symmetric norm is a per-edge scalar, so the whole layer is
    W A x for a fixed weighted adjacency A — ``resolve_dataflow`` picks
    W (A x) + b (aggregate_first) or A (W x) + b (transform_first); both
    lower through the fused gather->scale->aggregate pipeline."""
    src, dst = edge_endpoints(g)
    n = x.shape[0]
    edge_scale, self_scale = _gcn_scales(g)
    agg_first = resolve_dataflow(cfg) == "aggregate_first"
    h = x if agg_first else x @ params["w"]["w"]  # transform at min width
    aggr = agg_mod.gather_aggregate("sum", h, src, dst, n, g["valid_e"],
                                    edge_scale, precision=cfg.precision)
    aggr = aggr + h.astype(jnp.float32) * self_scale[:, None]  # self loop
    if agg_first:
        return linear(params["w"], aggr.astype(x.dtype))       # gamma
    return aggr.astype(x.dtype) + params["w"]["b"]


# ------------------------------------------------------------ GraphSAGE --
def sage_plan(cfg: ConvConfig, dtype=jnp.float32):
    return {
        "w_self": linear_plan(cfg.in_dim, cfg.out_dim, in_axis="embed",
                              out_axis="mlp", bias=True, dtype=dtype),
        "w_neigh": linear_plan(cfg.in_dim, cfg.out_dim, in_axis="embed",
                               out_axis="mlp", dtype=dtype),
    }


def sage_apply(params, g, x, cfg: ConvConfig):
    """x' = W1 x_v + W2 mean_u(x_u)  (flexible aggregation family).

    mean is linear, so W2 mean(x_u) == mean(W2 x_u) exactly —
    ``resolve_dataflow`` aggregates at min(F_in, F_out) width."""
    src, dst = edge_endpoints(g)
    agg_first = resolve_dataflow(cfg) == "aggregate_first"
    h = x if agg_first else x @ params["w_neigh"]["w"]
    aggr = agg_mod.gather_aggregate("mean", h, src, dst, x.shape[0],
                                    g["valid_e"], precision=cfg.precision)
    neigh = linear(params["w_neigh"], aggr.astype(x.dtype)) if agg_first \
        else aggr.astype(x.dtype)
    return linear(params["w_self"], x) + neigh


# ------------------------------------------------------------- GIN(E) ---
def gin_plan(cfg: ConvConfig, dtype=jnp.float32):
    p = {
        "eps": ParamSpec((), jnp.float32, (), init="zeros"),
        "mlp1": linear_plan(cfg.in_dim, cfg.out_dim, in_axis="embed",
                            out_axis="mlp", bias=True, dtype=dtype),
        "mlp2": linear_plan(cfg.out_dim, cfg.out_dim, in_axis="mlp",
                            out_axis="mlp", bias=True, dtype=dtype),
    }
    if cfg.edge_dim:
        p["w_edge"] = linear_plan(cfg.edge_dim, cfg.in_dim, in_axis=None,
                                  out_axis="embed", dtype=dtype)
    return p


def gin_apply(params, g, x, cfg: ConvConfig):
    """x' = MLP((1+eps) x_v + sum_u relu(x_u + W_e e_uv)) — edge features
    make this inexpressible as SpMM (paper Table II)."""
    src, dst = edge_endpoints(g)
    if "w_edge" in params:
        # edge-feature phi is nonlinear per edge: keep the materialized
        # message path (the fused kernel's scale slot cannot express it)
        msg = jax.nn.relu(_gather(x, src)
                          + linear(params["w_edge"], g["edge_feat"]))
        aggr = agg_mod.segment_aggregate("sum", msg, dst, x.shape[0],
                                         g["valid_e"],
                                         precision=cfg.precision)
    else:
        aggr = agg_mod.gather_aggregate("sum", x, src, dst, x.shape[0],
                                        g["valid_e"],
                                        precision=cfg.precision)
    h = (1.0 + params["eps"]) * x + aggr.astype(x.dtype)
    h = act(cfg.activation)(linear(params["mlp1"], h))
    return linear(params["mlp2"], h)


# ---------------------------------------------------------------- PNA ---
def pna_plan(cfg: ConvConfig, dtype=jnp.float32):
    tower_in = cfg.in_dim * len(PNA_AGGS) * len(PNA_SCALERS)
    p = {
        "pre": linear_plan(2 * cfg.in_dim + cfg.edge_dim, cfg.in_dim,
                           in_axis="embed", out_axis="mlp", bias=True,
                           dtype=dtype),
        "post": linear_plan(tower_in + cfg.in_dim, cfg.out_dim,
                            in_axis="embed", out_axis="mlp", bias=True,
                            dtype=dtype),
    }
    return p


def pna_apply(params, g, x, cfg: ConvConfig):
    """Principal Neighbourhood Aggregation: message MLP phi(x_v, x_u, e),
    4 aggregators x 3 degree scalers, then gamma on [x_v ; towers]."""
    src, dst = edge_endpoints(g)
    n = x.shape[0]
    h_src = _gather(x, src)
    h_dst = _gather(x, dst)
    feats = [h_dst, h_src]
    if cfg.edge_dim:
        feats.append(g["edge_feat"].astype(x.dtype))
    msg = act(cfg.activation)(
        linear(params["pre"], jnp.concatenate(feats, axis=-1)))
    towers = [agg_mod.segment_aggregate(a, msg, dst, n, g["valid_e"],
                                        precision=cfg.precision)
              for a in PNA_AGGS]
    deg = jnp.maximum(g["in_deg"], 1.0)
    logd = jnp.log(deg + 1.0)[:, None]
    scaled = []
    for t in towers:
        scaled += [t, t * (logd / cfg.delta), t * (cfg.delta / logd)]
    out = jnp.concatenate([x.astype(jnp.float32)] + scaled, axis=-1)
    return linear(params["post"], out.astype(x.dtype))


# ---------------------------------------------------------------- GAT ---
def gat_plan(cfg: ConvConfig, dtype=jnp.float32):
    p = {
        "w": linear_plan(cfg.in_dim, cfg.out_dim, in_axis="embed",
                         out_axis="mlp", bias=True, dtype=dtype),
        "w_self": linear_plan(cfg.in_dim, cfg.out_dim, in_axis="embed",
                              out_axis="mlp", dtype=dtype),
        "a_src": ParamSpec((cfg.out_dim,), dtype, ("mlp",)),
        "a_dst": ParamSpec((cfg.out_dim,), dtype, ("mlp",)),
    }
    if cfg.edge_dim:
        p["a_edge"] = linear_plan(cfg.edge_dim, 1, in_axis=None,
                                  out_axis=None, dtype=dtype)
    return p


def gat_apply(params, g, x, cfg: ConvConfig):
    """x' = W_self x_v + sum_u alpha_uv (W x_u) + b, with
    alpha = softmax_v(LeakyReLU(a_src.(W x_u) + a_dst.(W x_v)
    [+ a_e.e_uv])) — the root-weight GAT variant (no implicit self
    loops; the explicit W_self path keeps isolated nodes informative).

    The per-dst softmax is the new reduction shape: logits stream
    through ``segment_softmax`` (per-segment online max/exp-sum — the
    ``kernels/segment_softmax`` Pallas machine under backend="pallas"),
    and the resulting per-edge weight rides the fused gather tier's
    existing scale slot, exactly where the GCN symmetric norm sits — so
    the (E, F) message tensor still never materializes on the Pallas
    path. Attention math is fp32 at every PrecisionPolicy: bf16/int8
    quantize the projection and the aggregate message stream only (the
    documented int8 exclusion, docs/KERNELS.md)."""
    src, dst = edge_endpoints(g)
    n = x.shape[0]
    h = x @ params["w"]["w"]                   # projection (policy width)
    hf = h.astype(jnp.float32)
    s_src = hf @ params["a_src"].astype(jnp.float32)
    s_dst = hf @ params["a_dst"].astype(jnp.float32)
    logits = _gather(s_src, src) + _gather(s_dst, dst)
    if "a_edge" in params:
        logits = logits + (g["edge_feat"].astype(jnp.float32)
                           @ params["a_edge"]["w"].astype(
                               jnp.float32))[:, 0]
    logits = jax.nn.leaky_relu(logits, 0.2)
    alpha = agg_mod.segment_softmax(logits, dst, n, g["valid_e"])
    aggr = agg_mod.gather_aggregate("sum", h, src, dst, n, g["valid_e"],
                                    alpha, precision=cfg.precision)
    return linear(params["w_self"], x) + aggr.astype(x.dtype) \
        + params["w"]["b"]


# ------------------------------------------------------------ GatedGCN ---
def gatedgcn_plan(cfg: ConvConfig, dtype=jnp.float32):
    p = {k: linear_plan(cfg.in_dim, cfg.out_dim, in_axis="embed",
                        out_axis="mlp", bias=True, dtype=dtype)
         for k in ("A", "B", "D", "E")}
    if cfg.edge_dim:
        p["C"] = linear_plan(cfg.edge_dim, cfg.out_dim, in_axis=None,
                             out_axis="mlp", bias=True, dtype=dtype)
    return p


def gatedgcn_apply(params, g, x, cfg: ConvConfig):
    """Residual gated graph conv, with ``i`` the destination and ``j``
    the source of each edge:
    ``e'_ij = D x_i + E x_j + C e_ij``, ``s_ij = sigmoid(e'_ij)``,
    ``x'_i = A x_i + sum_j s_ij * B x_j / (sum_j s_ij + 1e-6)``.

    The gate is per edge and per channel, so it cannot ride the fused
    gather tier's one scalar per edge: messages and gates materialise
    and one segment sum over ``[s * B x_j ; s]`` gives numerator and
    denominator (PNA's path), that stream at the layer's width. Returns
    ``(x', e')``, ``e'`` before any activation."""
    src, dst = edge_endpoints(g)
    n, f = x.shape[0], cfg.out_dim
    e = g.get("edge_state", g.get("edge_feat"))
    e_hat = _gather(linear(params["D"], x), dst) \
        + _gather(linear(params["E"], x), src)
    if "C" in params:
        e_hat = e_hat + linear(params["C"], e.astype(x.dtype))
    gate = jax.nn.sigmoid(e_hat.astype(jnp.float32))
    msg = gate * _gather(linear(params["B"], x), src).astype(jnp.float32)
    sums = agg_mod.segment_aggregate(
        "sum", jnp.concatenate([msg, gate], axis=-1), dst, n, g["valid_e"],
        precision=cfg.precision).astype(jnp.float32)
    out = linear(params["A"], x).astype(jnp.float32) \
        + sums[:, :f] / (sums[:, f:] + 1e-6)
    return out.astype(x.dtype), e_hat


register_conv("gcn", gcn_plan, gcn_apply, reorderable=True, resident=True,
              partition_bitwise=True)
register_conv("sage", sage_plan, sage_apply, reorderable=True,
              resident=True)
register_conv("gin", gin_plan, gin_apply)
register_conv("pna", pna_plan, pna_apply)
register_conv("gat", gat_plan, gat_apply, attention=True,
              partition_bitwise=True)
register_conv("gatedgcn", gatedgcn_plan, gatedgcn_apply, edge_state=True,
              dse=False)


def conv_plan(cfg: ConvConfig, dtype=jnp.float32):
    return conv_spec(cfg.conv).plan(cfg, dtype)


def conv_apply(params, g, x, cfg: ConvConfig):
    return conv_spec(cfg.conv).apply(params, g, x, cfg)
