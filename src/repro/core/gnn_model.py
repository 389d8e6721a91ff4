"""GNNModel — the paper's parameterized model architecture (§IV, Fig. 2).

GNN backbone (conv layers + activation + optional skip connections) ->
global pooling (concat of sum/mean/max) -> MLP prediction head. Node- and
graph-level tasks; node and edge input features; arbitrary activation;
per-layer parallelism factors (gnn_p_in/hidden/out, mlp p_in/hidden/out)
which map to kernel tile sizes on TPU.

The paper's Listing-1 API shape is preserved: a single config object the
user trains against (here: init/apply over padded graphs), handed to
``core.project.Project`` for accelerator generation.

Execution tiers: ``apply`` (padded per-graph oracle) -> ``apply_packed``
(one jitted program over a packed GraphBatch) -> ``apply_packed_sharded``
(one SPMD program over a ("data",) device mesh, each device consuming
its own GraphBatch shard — see DESIGN_BATCHING.md §Sharded waves).

Precision: ``gnn_precision`` names the model's PrecisionPolicy (fp32 |
bf16 | int8; ``apply``/``apply_packed`` also accept a fully resolved —
possibly calibrated — ``PrecisionPolicy`` via ``policy=``). Each layer
runs its datapath (weights, streamed activations, kernel tiles) at the
layer's compute width while the residual stream, skip connections, and
pooling stay fp32 — the standard master-precision mixed-precision
discipline. The legacy ``quant`` hook (uniform FPX fake-quant after
every op) is kept as the paper's original testbench semantic.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import convs as C
from repro.core import quantization as Q
from repro.core.pooling import global_pooling, segment_global_pooling
from repro.nn.layers import act, linear, linear_plan


@dataclasses.dataclass(frozen=True)
class MLPConfig:
    in_dim: int
    out_dim: int
    hidden_dim: int = 64
    hidden_layers: int = 2
    activation: str = "relu"
    p_in: int = 1
    p_hidden: int = 1
    p_out: int = 1


@dataclasses.dataclass(frozen=True)
class GNNModelConfig:
    """Mirrors gnnb.GNNModel(...) keyword-for-keyword where sensible."""
    graph_input_feature_dim: int
    graph_input_edge_dim: int = 0
    gnn_hidden_dim: int = 64
    gnn_num_layers: int = 2
    gnn_output_dim: int = 64
    gnn_conv: str = "gcn"           # any registered conv (convs.CONV_TYPES)
    gnn_activation: str = "relu"
    gnn_skip_connection: bool = True
    global_pooling: tuple = ("add", "mean", "max")
    mlp_head: MLPConfig | None = None
    output_activation: str | None = None
    task: str = "graph"                      # graph | node
    gnn_p_in: int = 1
    gnn_p_hidden: int = 8
    gnn_p_out: int = 4
    pna_delta: float = 1.0
    # transform/aggregate ordering for the linear convs (convs.DATAFLOWS);
    # "auto" lets the per-layer cost model pick, the explicit values
    # force one ordering for the whole stack
    gnn_dataflow: str = "auto"
    avg_degree: float = 2.0
    # datapath precision spec (quantization.PRECISIONS); resolved to a
    # per-layer PrecisionPolicy by apply/apply_packed (or overridden by
    # their policy= argument with a calibrated policy)
    gnn_precision: str = "fp32"

    def conv_cfg(self, layer: int) -> C.ConvConfig:
        ind = self.graph_input_feature_dim if layer == 0 \
            else self.gnn_hidden_dim
        outd = self.gnn_output_dim if layer == self.gnn_num_layers - 1 \
            else self.gnn_hidden_dim
        p_in = self.gnn_p_in if layer == 0 else self.gnn_p_hidden
        p_out = self.gnn_p_out if layer == self.gnn_num_layers - 1 \
            else self.gnn_p_hidden
        return C.ConvConfig(in_dim=ind, out_dim=outd,
                            edge_dim=self.graph_input_edge_dim,
                            conv=self.gnn_conv,
                            activation=self.gnn_activation,
                            p_in=p_in, p_out=p_out, delta=self.pna_delta,
                            dataflow=self.gnn_dataflow,
                            avg_degree=self.avg_degree)

    @property
    def pooled_dim(self) -> int:
        return self.gnn_output_dim * len(self.global_pooling)


def mlp_head_plan(cfg: MLPConfig, dtype=jnp.float32):
    dims = [cfg.in_dim] + [cfg.hidden_dim] * cfg.hidden_layers \
        + [cfg.out_dim]
    return {f"l{i}": linear_plan(dims[i], dims[i + 1], in_axis=None,
                                 out_axis=None, bias=True, dtype=dtype)
            for i in range(len(dims) - 1)}


def mlp_head_apply(params, x, cfg: MLPConfig, quant: Q.FPX | None = None,
                   lp: Q.LayerPrecision | None = None,
                   record: list | None = None):
    """lp (the policy's head precision) runs the head matmuls at the
    compute width — bf16 casts; int8 re-quantizes the *hidden*
    activations onto the head grid after each linear (the fake-quant
    emulation of an int8 MAC array), while the final accumulator output
    leaves the head dequantized in fp32 — and returns fp32.

    record: when a list, appends each hidden layer's pre-activation
    max-abs — the calibration probe for the head's act grid, kept
    inside the real head path so it can never desynchronize from it."""
    if lp is not None and lp.compute != "fp32":
        params = lp.cast_params(params)
        x = lp.cast_activation(x)
    n = cfg.hidden_layers + 1
    for i in range(n):
        x = linear(params[f"l{i}"], x)
        if quant is not None:
            x = Q.quantize(x, quant)
        if i < n - 1:
            if record is not None:
                record.append(jnp.max(jnp.abs(x)))
            if lp is not None and lp.compute == "int8":
                x = Q.quantize(x, lp.act_fpx)
            x = act(cfg.activation)(x)
    return x.astype(jnp.float32)


def model_plan(cfg: GNNModelConfig, dtype=jnp.float32):
    plan = {"convs": {f"c{i}": C.conv_plan(cfg.conv_cfg(i), dtype)
                      for i in range(cfg.gnn_num_layers)}}
    if cfg.gnn_skip_connection:
        # project skip when dims change (layer0 and final layer)
        for i in range(cfg.gnn_num_layers):
            cc = cfg.conv_cfg(i)
            if cc.in_dim != cc.out_dim:
                plan[f"skip{i}"] = linear_plan(cc.in_dim, cc.out_dim,
                                               in_axis=None, out_axis=None,
                                               dtype=dtype)
    if cfg.task == "graph":
        plan["mlp"] = mlp_head_plan(cfg.mlp_head, dtype)
    return plan


def graph_inputs(batch_el: dict) -> tuple:
    """Unpack one padded graph {node_feat, edge_index, edge_feat,
    num_nodes, num_edges, y} into (g, x, node_mask)."""
    x = batch_el["node_feat"]
    n_max = x.shape[0]
    num_nodes = batch_el["num_nodes"]
    edge_index = batch_el["edge_index"]
    valid_e = edge_index[:, 0] >= 0
    node_mask = jnp.arange(n_max) < num_nodes
    from repro.core.aggregations import degrees
    indeg, outdeg = degrees(edge_index, n_max, valid_e)
    edge_scale, self_scale = C.gcn_normalization(edge_index, indeg, valid_e)
    g = {"edge_index": edge_index, "edge_feat": batch_el.get("edge_feat"),
         "valid_e": valid_e, "in_deg": indeg, "out_deg": outdeg,
         "num_nodes": num_nodes,
         # GCN symmetric-norm scales, hoisted: derived once per batch
         # from static graph fields instead of twice per layer stack
         "gcn_edge_scale": edge_scale, "gcn_self_scale": self_scale}
    return g, x, node_mask


def packed_to_device(batch: dict) -> dict:
    """Host GraphBatch -> device arrays, stripping the host-only target
    buffer ``y`` so it is never traced into the inference program."""
    return {k: jnp.asarray(v) for k, v in batch.items() if k != "y"}


def packed_inputs(batch: dict) -> tuple:
    """Unpack a packed GraphBatch {node_feat (N,F), node_graph_id (N,),
    edge_index (E,2) global ids, edge_feat, graph_valid (G,)} into
    (g, x, node_mask, graph_id). The packed batch is the disjoint union
    graph, so the same conv applies run on it unchanged."""
    x = batch["node_feat"]
    graph_id = batch["node_graph_id"]
    num_graphs = batch["graph_valid"].shape[0]
    node_mask = graph_id < num_graphs
    edge_index = batch["edge_index"]
    valid_e = edge_index[:, 0] >= 0
    # partitioned subgraphs carry precomputed *global* degrees: a halo
    # row's in-edges live on its owning device, so the locally-counted
    # degree would be wrong for the GCN norm of cut edges
    indeg = batch.get("node_in_deg")
    outdeg = batch.get("node_out_deg")
    if indeg is None or outdeg is None:
        from repro.core.aggregations import degrees
        d_in, d_out = degrees(edge_index, x.shape[0], valid_e)
        indeg = d_in if indeg is None else indeg
        outdeg = d_out if outdeg is None else outdeg
    edge_scale, self_scale = C.gcn_normalization(edge_index, indeg, valid_e)
    g = {"edge_index": edge_index, "edge_feat": batch.get("edge_feat"),
         "valid_e": valid_e, "in_deg": indeg, "out_deg": outdeg,
         "num_nodes": jnp.sum(node_mask.astype(jnp.int32)),
         "gcn_edge_scale": edge_scale, "gcn_self_scale": self_scale}
    return g, x, node_mask, graph_id


def resolve_policy(cfg: GNNModelConfig,
                   policy=None) -> Q.PrecisionPolicy:
    """The model's resolved PrecisionPolicy: an explicit (possibly
    calibrated) policy wins, else ``cfg.gnn_precision`` resolves to a
    uniform per-layer policy."""
    return Q.resolve_policy(policy if policy is not None
                            else cfg.gnn_precision, cfg.gnn_num_layers)


def _backbone(params, cfg: GNNModelConfig, g, x, node_mask,
              quant: Q.FPX | None,
              policy: Q.PrecisionPolicy | None = None,
              record: list | None = None, exchange=None):
    """Conv stack + activation + skip, shared by the padded per-graph
    oracle (`apply`) and the packed batch path (`apply_packed`).

    policy: each layer's conv datapath (weights + the tensors entering
    the edge stream) runs at the layer's compute width; the residual
    stream / skip / activation stay fp32. record: when a list, appends
    one max-abs scalar per layer (max over the layer's input and conv
    output) — the calibration probe ``activation_ranges`` consumes.
    exchange: optional (N, F) -> (N, F) hook run between consecutive
    layers (not after the last) — the partitioned path's halo exchange,
    which overwrites replicated boundary rows with their owners' values
    so layer i+1 aggregates over up-to-date neighbors.
    """
    nl = cfg.gnn_num_layers
    for i in range(nl):
        cc = cfg.conv_cfg(i)
        p_i = params["convs"][f"c{i}"]
        x_in = x
        lp = policy.layer(i) if policy is not None else None
        if lp is not None and lp.compute != "fp32":
            cc = dataclasses.replace(cc, precision=lp)
            p_i = lp.cast_params(p_i)
            x_in = lp.cast_activation(x)
        h = C.conv_apply(p_i, g, x_in, cc).astype(jnp.float32)
        if record is not None:
            record.append(jnp.maximum(jnp.max(jnp.abs(x)),
                                      jnp.max(jnp.abs(h))))
        if quant is not None:
            h = Q.quantize(h, quant)
        if cfg.gnn_skip_connection:
            skip = x
            if f"skip{i}" in params:
                skip = linear(params[f"skip{i}"], x)
            h = h + skip
        x = act(cfg.gnn_activation)(h)
        x = x * node_mask[:, None]
        if quant is not None:
            x = Q.quantize(x, quant)
        if exchange is not None and i < nl - 1:
            x = exchange(x)
    return x


def apply(params, cfg: GNNModelConfig, batch_el: dict,
          quant: Q.FPX | None = None, policy=None):
    """Forward one padded graph. quant != None reproduces the fixed-point
    testbench semantics (weights are pre-quantized by the caller);
    policy (or cfg.gnn_precision) selects the per-layer PrecisionPolicy
    datapath."""
    pol = resolve_policy(cfg, policy)
    pol = None if pol.is_fp32 else pol
    g, x, node_mask = graph_inputs(batch_el)
    if quant is not None:
        x = Q.quantize(x, quant)
    x = _backbone(params, cfg, g, x, node_mask, quant, pol)
    if cfg.task == "node":
        return x
    pooled = global_pooling(cfg.global_pooling, x, node_mask)
    if quant is not None:
        pooled = Q.quantize(pooled, quant)
    out = mlp_head_apply(params["mlp"], pooled.astype(x.dtype),
                         cfg.mlp_head, quant,
                         pol.head if pol is not None else None)
    if cfg.output_activation:
        out = act(cfg.output_activation)(out)
    return out


def apply_packed(params, cfg: GNNModelConfig, batch: dict,
                 quant: Q.FPX | None = None, policy=None, *,
                 halo_exchange=None, return_node_features: bool = False):
    """Forward a packed GraphBatch — all graphs in one XLA program.

    Returns (num_graphs, out_dim) for graph tasks (rows where
    ``graph_valid`` is False are padding) or the (N_total, F) node
    embeddings for node tasks. Matches per-graph ``apply`` outputs to
    fp32 tolerance; `apply` stays the single-graph oracle. policy (or
    cfg.gnn_precision) selects the per-layer PrecisionPolicy datapath —
    both paths resolve it identically, so padded-vs-packed parity holds
    at every precision.

    halo_exchange: optional between-layer (N, F) -> (N, F) hook (the
    partitioned path's boundary-row swap; see
    ``make_partitioned_apply``). return_node_features skips pooling and
    the head, returning the post-backbone (N, F) node table — the
    per-device body of the partitioned program, which pools only after
    reassembling the global node order.
    """
    pol = resolve_policy(cfg, policy)
    pol = None if pol.is_fp32 else pol
    g, x, node_mask, graph_id = packed_inputs(batch)
    num_graphs = batch["graph_valid"].shape[0]
    if quant is not None:
        x = Q.quantize(x, quant)
    x = _backbone(params, cfg, g, x, node_mask, quant, pol,
                  exchange=halo_exchange)
    if cfg.task == "node" or return_node_features:
        return x
    pooled = segment_global_pooling(cfg.global_pooling, x, graph_id,
                                    num_graphs, node_mask)
    if quant is not None:
        pooled = Q.quantize(pooled, quant)
    out = mlp_head_apply(params["mlp"], pooled.astype(x.dtype),
                         cfg.mlp_head, quant,
                         pol.head if pol is not None else None)
    if cfg.output_activation:
        out = act(cfg.output_activation)(out)
    return out


def _qp_row(lp: Q.LayerPrecision | None):
    """Per-layer precision row [mode, scale, lo, hi] the residency
    kernel's dynamic cast consumes (residency._cast_dyn) — the exact
    parameters of ``LayerPrecision.cast_activation`` for this layer."""
    if lp is None or lp.compute == "fp32":
        return [0.0, 1.0, 0.0, 0.0]
    if lp.compute == "bf16":
        return [1.0, 1.0, 0.0, 0.0]
    fpx = lp.in_fpx or lp.act_fpx
    return [2.0, fpx.resolution, fpx.min_val, fpx.max_val]


def _pad2(w, fmax):
    return jnp.zeros((fmax, fmax), jnp.float32).at[
        :w.shape[0], :w.shape[1]].set(w.astype(jnp.float32))


def apply_packed_resident(params, cfg: GNNModelConfig, batch: dict,
                          quant: Q.FPX | None = None, policy=None, *,
                          fusion_depth: int = 2,
                          edge_block: int | None = None,
                          interpret: bool | None = None,
                          vmem_bytes: int | None = None):
    """``apply_packed`` with the conv stack executed by the multi-layer
    VMEM-residency kernel: consecutive layers fuse into single kernel
    launches (groups of ``fusion_depth``), the node table staying
    on-chip across layer boundaries instead of round-tripping HBM per
    layer (kernels/fused_gather_aggregate/residency.py).

    Falls back to ``apply_packed`` — bit-identically, since that *is*
    the fallback call — whenever the ``convs.residency_plan`` VMEM
    budget rule says residency is illegal (non-linear-phi conv,
    fusion_depth < 2, working set over budget) or the legacy ``quant``
    testbench hook is set. The resident path always aggregates first at
    the padded table width: exact for fp32 (linearity), within the
    layer dtype's rounding tolerance for bf16/int8 policies (the
    per-layer PrecisionPolicy is emulated in-kernel via dynamic qp rows;
    see docs/KERNELS.md §Residency). Pooling + MLP head run unchanged.
    """
    from repro.core import aggregations as agg_mod
    from repro.kernels.fused_gather_aggregate.residency import (
        fused_layer_stack_pallas)

    pol = resolve_policy(cfg, policy)
    pol = None if pol.is_fp32 else pol
    nl = cfg.gnn_num_layers
    ccs = [cfg.conv_cfg(i) for i in range(nl)]
    eb = edge_block or agg_mod._DEFAULT_EDGE_BLOCK
    g, x, node_mask, graph_id = packed_inputs(batch)
    n = x.shape[0]
    plan = C.residency_plan([(c.in_dim, c.out_dim) for c in ccs], n,
                            cfg.gnn_conv, fusion_depth,
                            quantized=pol is not None,
                            vmem_bytes=vmem_bytes)
    if quant is not None or not plan.legal:
        return apply_packed(params, cfg, batch, quant, policy)

    fmax = plan.fmax
    src, dst = g["edge_index"][:, 0], g["edge_index"][:, 1]
    if cfg.gnn_conv == "gcn":
        scale = g["gcn_edge_scale"]
        self_vec = g["gcn_self_scale"]
    else:                                        # sage
        scale = g["valid_e"].astype(jnp.float32)
        self_vec = jnp.zeros((n,), jnp.float32)
    xpad = jnp.zeros((n, fmax), jnp.float32).at[:, :x.shape[1]].set(
        x.astype(jnp.float32))

    for i0 in range(0, nl, plan.depth):
        layers = range(i0, min(i0 + plan.depth, nl))
        wa, wn, wsk, bias, qps = [], [], [], [], []
        for i in layers:
            p_i = params["convs"][f"c{i}"]
            lp = pol.layer(i) if pol is not None else None
            if lp is not None and lp.compute != "fp32":
                p_i = lp.cast_params(p_i)
            qps.append(_qp_row(lp))
            if cfg.gnn_conv == "gcn":
                wa.append(jnp.zeros((fmax, fmax), jnp.float32))
                wn.append(_pad2(p_i["w"]["w"], fmax))
                b_i = p_i["w"]["b"]
            else:
                wa.append(_pad2(p_i["w_self"]["w"], fmax))
                wn.append(_pad2(p_i["w_neigh"]["w"], fmax))
                b_i = p_i["w_self"]["b"]
            bias.append(jnp.zeros((fmax,), jnp.float32).at[
                :b_i.shape[0]].set(b_i.astype(jnp.float32)))
            if not cfg.gnn_skip_connection:
                wsk.append(jnp.zeros((fmax, fmax), jnp.float32))
            elif f"skip{i}" in params:
                # projection skips stay fp32 (the residual-stream rule)
                wsk.append(_pad2(params[f"skip{i}"]["w"], fmax))
            else:
                wsk.append(_pad2(jnp.eye(ccs[i].in_dim), fmax))
        xpad = fused_layer_stack_pallas(
            xpad, src, dst, scale, self_vec,
            node_mask.astype(jnp.float32),
            jnp.stack(wa), jnp.stack(wn), jnp.stack(wsk),
            jnp.stack(bias), jnp.asarray(qps, jnp.float32),
            kind=cfg.gnn_conv, activation=cfg.gnn_activation,
            edge_block=eb,
            interpret=agg_mod.resolve_interpret(interpret),
            has_skip=cfg.gnn_skip_connection,
            quantized=pol is not None)

    x = xpad[:, :ccs[-1].out_dim]
    if cfg.task == "node":
        return x
    num_graphs = batch["graph_valid"].shape[0]
    pooled = segment_global_pooling(cfg.global_pooling, x, graph_id,
                                    num_graphs, node_mask)
    out = mlp_head_apply(params["mlp"], pooled, cfg.mlp_head, None,
                         pol.head if pol is not None else None)
    if cfg.output_activation:
        out = act(cfg.output_activation)(out)
    return out


def stack_shards(shards) -> dict:
    """Host ShardedBatch shards -> one stacked device-ready dict with a
    leading shard dim (num_shards, ...), stripping the host-only ``y``
    like ``packed_to_device``. Accepts a ShardedBatch or a plain list of
    same-shape GraphBatch dicts."""
    shards = getattr(shards, "shards", shards)
    return {k: jnp.stack([jnp.asarray(b[k]) for b in shards])
            for k in shards[0] if k != "y"}


def make_sharded_apply(cfg: GNNModelConfig, mesh,
                       quant: Q.FPX | None = None, policy=None):
    """Build the jitted SPMD program for data-parallel sharded packed
    inference over a 1-D ("data",) mesh (launch.mesh.make_data_mesh).

    Params replicate (distributed.sharding.replicated); the stacked
    batch's leading shard dim splits over "data" (graph_batch_sharding)
    so each device consumes exactly its own GraphBatch shard — the
    per-device program is ``apply_packed`` unchanged, which is why
    sharded outputs match the single-device program to fp32 tolerance
    at every precision and aggregation backend. Graph tasks return
    (num_shards, max_graphs, out_dim) — restore host order with
    ``data.pipeline.gather_shard_outputs``; node tasks return the
    stacked per-shard node tables (num_shards, node_budget, F).

    Trace-time state (the aggregation backend scope) is baked in on the
    first call, like ``apply_packed`` under jit. Hold on to the returned
    callable across waves so XLA compiles exactly once.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import (graph_batch_sharding,
                                            replicated)

    def per_shard(params, batch):
        batch = {k: v[0] for k, v in batch.items()}
        return apply_packed(params, cfg, batch, quant, policy)[None]

    fn = jax.shard_map(per_shard, mesh=mesh,
                       in_specs=(P(), P("data")), out_specs=P("data"),
                       check_vma=False)
    return jax.jit(fn, in_shardings=(replicated(mesh),
                                     graph_batch_sharding(mesh)))


def apply_packed_sharded(params, cfg: GNNModelConfig, shards, mesh=None,
                         quant: Q.FPX | None = None, policy=None):
    """One-shot data-parallel sharded forward: stack ``shards`` (a
    ShardedBatch, a list of same-shape GraphBatch dicts, or an already
    stacked dict) and run them through one SPMD program, one shard per
    device. ``mesh=None`` builds the ("data",) mesh over the first
    num_shards local devices. Retraces on every call — serving and
    benchmark loops should hold on to ``make_sharded_apply`` instead."""
    stacked = shards if isinstance(shards, dict) else stack_shards(shards)
    if mesh is None:
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh(stacked["node_feat"].shape[0])
    return make_sharded_apply(cfg, mesh, quant, policy)(params, stacked)


def make_partitioned_apply(cfg: GNNModelConfig, mesh,
                           quant: Q.FPX | None = None, policy=None, *,
                           out_rows: int | None = None):
    """Build the jitted SPMD program for intra-graph partitioned
    inference: ONE oversize graph split into per-device subgraphs
    (``data.pipeline.partition_graph``) runs over the same 1-D
    ("data",) mesh as the sharded path.

    The per-device body is ``apply_packed`` unchanged (conv x precision
    x backend parity by construction) with two additions fused around
    it:

    * **halo exchange** between conv layers: each device publishes its
      ``halo_send`` boundary rows, the (halo_budget, F) publish buffers
      all-gather over "data", and every device overwrites its halo rows
      (``halo_recv_src``/``halo_recv_dst``; sentinel indices drop) with
      the owners' freshly-computed values — so layer i+1 aggregates
      over exact neighbor features despite the edge cut;
    * **global reassembly** after the last layer: the per-device node
      tables scatter into global node order via ``node_global_id``
      (each owned row written exactly once), then the padded oracle's
      own ``global_pooling`` + head run over the reassembled buffer —
      which is why partitioned graph outputs match ``apply`` bitwise
      at fp32.

    The build is TWO programs, not one: the SPMD conv stack over the
    mesh, and a single-device tail doing the O(out_rows) reassembly +
    pooling + head. Folding the tail into the SPMD program would
    replicate its full-graph-sized scatter and reductions on every
    device — dead weight that grows with the graph while the per-device
    conv work shrinks with it. The tail is exactly the work the padded
    oracle's own epilogue pays, paid once.

    out_rows sizes the reassembly buffer; pass the source graph's
    padded node-buffer row count (``GraphPartition.padded_nodes``) for
    *bitwise* fp32 parity with the padded oracle — XLA's pooling
    reduction is shape-sensitive, so reducing over a buffer of any
    other size matches only to reassociation tolerance. Defaults to
    ``num_parts * node_budget``.

    Returns ``fn(params, stacked_parts)``: graph tasks yield the
    (out_dim,) output row, node tasks the (out_rows, F) global-order
    node table.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import (graph_batch_sharding,
                                            replicated)

    def per_device(params, batch):
        b = {k: v[0] for k, v in batch.items()}
        send = b.pop("halo_send")
        recv_src = b.pop("halo_recv_src")
        recv_dst = b.pop("halo_recv_dst")
        b.pop("node_global_id")
        b.pop("total_nodes")
        nb = b["node_feat"].shape[0]

        def exchange(x):
            ok = send >= 0
            pub = jnp.where(ok[:, None], x[jnp.clip(send, 0, nb - 1)], 0.0)
            flat = jax.lax.all_gather(pub, "data").reshape(-1, x.shape[-1])
            rows = flat[jnp.clip(recv_src, 0, flat.shape[0] - 1)]
            return x.at[recv_dst].set(rows, mode="drop")

        feats = apply_packed(params, cfg, b, quant, policy,
                             halo_exchange=exchange,
                             return_node_features=True)
        return feats[None]

    conv = jax.shard_map(per_device, mesh=mesh,
                         in_specs=(P(), P("data")), out_specs=P("data"),
                         check_vma=False)
    conv = jax.jit(conv, in_shardings=(replicated(mesh),
                                       graph_batch_sharding(mesh)))

    def tail(params, tbl, gids, total):
        fdim = tbl.shape[-1]
        rows = out_rows or tbl.shape[0] * tbl.shape[1]
        buf = jnp.zeros((rows, fdim), tbl.dtype)
        buf = buf.at[gids.reshape(-1)].set(tbl.reshape(-1, fdim),
                                           mode="drop")
        if cfg.task == "node":
            return buf
        mask = jnp.arange(buf.shape[0]) < total
        pol = resolve_policy(cfg, policy)
        pol = None if pol.is_fp32 else pol
        pooled = global_pooling(cfg.global_pooling, buf, mask)
        if quant is not None:
            pooled = Q.quantize(pooled, quant)
        out = mlp_head_apply(params["mlp"], pooled.astype(buf.dtype),
                             cfg.mlp_head, quant,
                             pol.head if pol is not None else None)
        if cfg.output_activation:
            out = act(cfg.output_activation)(out)
        return out

    tail = jax.jit(tail)

    def fn(params, stacked):
        tbl = conv(params, stacked)                      # (P, NB, F)
        # total_nodes rides as a traced arg, not a python constant —
        # every distinct graph size would otherwise recompile the tail
        return tail(params, tbl,
                    jnp.asarray(stacked["node_global_id"]),
                    jnp.asarray(stacked["total_nodes"])[0])

    return fn


#: compiled partitioned programs keyed by (config/mesh/quant/policy
#: identity, out_rows, num_parts); the value holds the keyed objects so
#: their ids cannot be recycled while the entry lives. Serving calls
#: ``apply_packed_partitioned`` per oversize request — without this, a
#: fresh ``jax.jit`` wrapper per call would recompile every time.
_PARTITIONED_PROGRAMS: dict = {}


def apply_packed_partitioned(params, cfg: GNNModelConfig, partition,
                             mesh=None, quant: Q.FPX | None = None,
                             policy=None):
    """One-shot partitioned forward of one oversize graph: stack a
    ``data.pipeline.GraphPartition``'s parts (or a plain list of
    same-shape part dicts), run the SPMD conv program + single-device
    reassembly tail over a ("data",) mesh (built over the first
    num_parts local devices when ``mesh=None``) and return the graph
    output row — the padded oracle's answer. The compiled programs are
    cached per (cfg, mesh, quant, policy, out_rows, num_parts), so
    serving loops can call this per request without recompiling."""
    parts = getattr(partition, "parts", partition)
    out_rows = getattr(partition, "padded_nodes", 0) or None
    stacked = stack_shards(parts)
    if mesh is None:
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh(len(parts))
    key = (id(cfg), id(mesh), id(quant), id(policy), out_rows, len(parts))
    hit = _PARTITIONED_PROGRAMS.get(key)
    if hit is None:
        fn = make_partitioned_apply(cfg, mesh, quant, policy,
                                    out_rows=out_rows)
        hit = (fn, (cfg, mesh, quant, policy))
        _PARTITIONED_PROGRAMS[key] = hit
    return hit[0](params, stacked)


def activation_ranges(params, cfg: GNNModelConfig, batch: dict) -> dict:
    """Calibration probe: one fp32 forward over a packed calibration
    batch, recording the max-abs ranges an int8 policy's grids are
    fitted from (``quantization.calibrate_policy``):

      acts[i]      — layer i's streamed tensors (conv input + output)
      weights[i]   — layer i's conv weight leaves
      head         — the pooled head input (graph tasks; 0.0 for node)
      head_hidden  — the head's hidden activations (a separate
                     per-tensor scale: add-pooling makes the input range
                     dwarf the hidden range)
      head_weight  — the MLP-head weight leaves
    """
    def tree_max_abs(tree):
        leaves = [jnp.max(jnp.abs(a)) for a in jax.tree_util.tree_leaves(
            tree) if jnp.issubdtype(a.dtype, jnp.floating)]
        return float(jnp.max(jnp.stack(leaves))) if leaves else 0.0

    g, x, node_mask, graph_id = packed_inputs(batch)
    rec: list = []
    x = _backbone(params, cfg, g, x, node_mask, None, None, record=rec)
    head_range = head_hidden = 0.0
    if cfg.task == "graph":
        num_graphs = batch["graph_valid"].shape[0]
        pooled = segment_global_pooling(cfg.global_pooling, x, graph_id,
                                        num_graphs, node_mask)
        head_range = float(jnp.max(jnp.abs(pooled)))
        head_rec: list = []
        mlp_head_apply(params["mlp"], pooled, cfg.mlp_head,
                       record=head_rec)
        if head_rec:
            head_hidden = float(jnp.max(jnp.stack(head_rec)))
    return {
        "acts": [float(r) for r in rec],
        "weights": [tree_max_abs(params["convs"][f"c{i}"])
                    for i in range(cfg.gnn_num_layers)],
        "head": head_range,
        "head_hidden": head_hidden,
        "head_weight": tree_max_abs(params.get("mlp", {})),
    }


def calibrated_policy(params, cfg: GNNModelConfig, batch: dict,
                      policy=None) -> Q.PrecisionPolicy:
    """Resolve + max-abs-calibrate the model's policy on one packed
    calibration batch (no-op beyond resolution for fp32/bf16)."""
    pol = resolve_policy(cfg, policy)
    if not pol.needs_calibration:
        return pol
    r = activation_ranges(params, cfg, batch)
    return Q.calibrate_policy(pol, r["acts"], r["weights"], r["head"],
                              r["head_weight"], r["head_hidden"])


def apply_batch(params, cfg: GNNModelConfig, batch: dict,
                quant: Q.FPX | None = None):
    """vmapped batched forward over stacked padded graphs."""
    return jax.vmap(lambda el: apply(params, cfg, el, quant))(
        {k: v for k, v in batch.items() if k != "y"})


def mse_loss(params, cfg: GNNModelConfig, batch: dict):
    pred = apply_batch(params, cfg, batch)
    return jnp.mean(jnp.square(pred - batch["y"]))


def mse_loss_packed(params, cfg: GNNModelConfig, batch: dict):
    """MSE over the valid graphs of a packed batch (padding rows masked)."""
    pred = apply_packed(params, cfg,
                        {k: v for k, v in batch.items() if k != "y"})
    w = batch["graph_valid"].astype(pred.dtype)[:, None]
    se = jnp.square(pred - batch["y"]) * w
    denom = jnp.maximum(jnp.sum(w) * pred.shape[-1], 1.0)
    return jnp.sum(se) / denom
