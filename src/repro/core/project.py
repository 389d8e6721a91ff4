"""Project — GNNBuilder's push-button accelerator-generation workflow
(paper §III, Listing 1), retargeted from Vitis HLS to XLA/TPU.

Stage mapping (DESIGN.md §2):
  gen_hw_model()             -> build + lower the specialized jitted
                                inference program (HLS codegen analogue)
  gen_testbench()            -> export dataset + float reference outputs
  build_and_run_testbench()  -> run the program over the dataset, report
                                MAE (fixed vs float) + measured runtime;
                                also drains the packed GraphBatch path
                                and reports throughput in graphs/s
  run_synthesis()            -> compile, then emit the synthesis report:
                                roofline latency, FLOPs, HBM/VMEM bytes
                                (the Vitis latency/BRAM report analogue),
                                plus the packed-batch program's modeled
                                graphs/s under the node/edge budget
All artifacts land in ``build_dir`` (config.json, report.json, HLO text),
the analogue of the HLS project directory.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import convs as Cv
from repro.core import gnn_model as G
from repro.core import quantization as Q
from repro.data import pipeline as data_mod
from repro.nn import param as prm


@dataclasses.dataclass(frozen=True)
class TPUTarget:
    """Hardware constants (v5e) — the ``fpga_part`` analogue."""
    name: str = "tpu-v5e"
    peak_flops: float = 197e12       # bf16
    hbm_bw: float = 819e9            # B/s
    link_bw: float = 50e9            # B/s per ICI link
    hbm_bytes: float = 16e9
    vmem_bytes: float = 128 * 2**20  # VMEM per core
    # fixed per-grid-step cost of a Pallas kernel invocation (dispatch +
    # block DMA setup) — the modeled quantity that makes tile-size knobs
    # observable to the DSE objective
    kernel_step_overhead: float = 100e-9

    def roofline_latency(self, flops: float, bytes_: float,
                         coll_bytes: float = 0.0) -> float:
        return max(flops / self.peak_flops, bytes_ / self.hbm_bw,
                   coll_bytes / self.link_bw)


class Project:
    def __init__(self, name: str, model_cfg: G.GNNModelConfig, task: str,
                 build_dir: str, dataset_cfg=None, max_nodes: int = 600,
                 max_edges: int = 600, num_nodes_guess: float = 18,
                 num_edges_guess: float = 38, degree_guess: float = 2.1,
                 float_or_fixed: str = "float", fpx: Q.FPX = Q.FPX(32, 16),
                 target: TPUTarget = TPUTarget(), n_jobs: int = 1,
                 seed: int = 0, batch_graphs: int = 32,
                 node_budget: int | None = None,
                 edge_budget: int | None = None,
                 edge_block: int = 128, node_block: int = 128,
                 agg_backend: str = "xla", dataflow: str | None = None,
                 precision=None, num_shards: int = 1,
                 gather_mode: str = "dma", fusion_depth: int = 1,
                 partition: int = 1):
        self.name = name
        # dataflow override + dataset degree flow into the per-layer
        # transform/aggregate planner (convs.resolve_dataflow);
        # precision (a name from quantization.PRECISIONS or a resolved
        # PrecisionPolicy) selects the per-layer datapath width
        cfg_updates = {"avg_degree": float(degree_guess)}
        if dataflow is not None:
            cfg_updates["gnn_dataflow"] = dataflow
        if isinstance(precision, str):
            cfg_updates["gnn_precision"] = precision
        self.cfg = dataclasses.replace(model_cfg, **cfg_updates)
        # resolved once per project; build_and_run_testbench max-abs
        # calibrates int8 grids on the testbench graphs before running
        self.policy = G.resolve_policy(
            self.cfg, precision if not isinstance(precision, str) else None)
        self.task = task
        self.build_dir = build_dir
        self.dataset_cfg = dataset_cfg or data_mod.GraphDataConfig(
            max_nodes=max_nodes, max_edges=max_edges,
            node_feat_dim=model_cfg.graph_input_feature_dim,
            edge_feat_dim=model_cfg.graph_input_edge_dim)
        self.max_nodes = max_nodes
        self.max_edges = max_edges
        self.num_nodes_guess = num_nodes_guess
        self.num_edges_guess = num_edges_guess
        self.degree_guess = degree_guess
        self.float_or_fixed = float_or_fixed
        self.fpx = fpx
        self.target = target
        self.seed = seed
        # packed GraphBatch execution budgets (DESIGN_BATCHING.md): the
        # flat buffers hold ~batch_graphs average graphs with 1.5x slack,
        # instead of batch_graphs * max_nodes worst-case padding.
        self.batch_graphs = batch_graphs
        self.node_budget = node_budget or data_mod.size_budget(
            batch_graphs, num_nodes_guess)
        self.edge_budget = edge_budget or data_mod.size_budget(
            batch_graphs, num_edges_guess)
        # segment-aggregation kernel tile sizes (DSE knobs, mirroring the
        # paper's parallelization factors) + backend selection
        self.edge_block = edge_block
        self.node_block = node_block
        self.agg_backend = agg_backend
        # gather kernel generation (aggregations.GATHER_MODES): "dma" =
        # the one-hot-free v2 kernel, "onehot" = the legacy contraction
        from repro.core.aggregations import GATHER_MODES
        if gather_mode not in GATHER_MODES:
            raise ValueError(f"gather_mode must be one of {GATHER_MODES}, "
                             f"got {gather_mode!r}")
        self.gather_mode = gather_mode
        # multi-layer VMEM residency: fusion_depth > 1 asks for the
        # resident conv stack; convs.residency_plan decides legality
        # against the kernels' scoped-VMEM limit at gen_hw_model time
        if fusion_depth < 1:
            raise ValueError(f"fusion_depth must be >= 1, "
                             f"got {fusion_depth}")
        self.fusion_depth = fusion_depth
        self.residency = None        # ResidencyPlan, set by gen_hw_model
        self.residency_engaged = False
        # data-parallel sharding: >1 splits each testbench/serving wave
        # into per-device packed shards over a ("data",) mesh, the
        # budgets above staying *per-shard* (graph-level partitioning —
        # the parallelization-factor knob one level above the kernels)
        if num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {num_shards}")
        self.num_shards = num_shards
        # intra-graph partitioning: >1 models serving ONE giant graph
        # split by edge cut across `partition` devices, each running the
        # per-shard packed program over its subgraph with per-layer halo
        # exchange (pipeline.partition_graph / apply_packed_partitioned).
        # Orthogonal to num_shards, which replicates whole graphs.
        if partition < 1:
            raise ValueError(f"partition must be >= 1, got {partition}")
        self.partition = partition
        self._fn = None
        self._fn_packed = None
        self._compiled = None
        self.params = None
        os.makedirs(build_dir, exist_ok=True)

    # ------------------------------------------------------- generation --
    def init_params(self, key=None):
        plan = G.model_plan(self.cfg)
        self.params = prm.materialize(
            plan, key if key is not None else jax.random.key(self.seed))
        return self.params

    def gen_hw_model(self):
        """Build the specialized inference program (codegen analogue)."""
        cfg = self.cfg
        quant = self.fpx if self.float_or_fixed == "fixed" else None
        backend = self.agg_backend

        def with_backend(apply_fn):
            # trace-time scope: segment_aggregate reads the process
            # default while the jitted program is being traced, so the
            # project's backend + tile choice is baked into its programs
            # without leaking to other projects in the same process
            def fn(params, batch):
                from repro.core import aggregations as agg_mod
                with agg_mod.backend_scope(backend, self.edge_block,
                                           self.node_block,
                                           gather_mode=self.gather_mode):
                    return apply_fn(params, batch)
            return fn

        policy = self.policy
        # multi-layer VMEM residency: the planner's budget rule decides
        # legality; the resident program additionally requires the Pallas
        # backend (it IS a Pallas kernel) and no legacy quant hook
        self.residency = Cv.residency_plan(
            [(cfg.conv_cfg(i).in_dim, cfg.conv_cfg(i).out_dim)
             for i in range(cfg.gnn_num_layers)],
            self.node_budget, cfg.gnn_conv, self.fusion_depth,
            quantized=not policy.is_fp32)
        resident = (self.residency.legal and self.fusion_depth > 1
                    and backend == "pallas" and quant is None)
        self.residency_engaged = resident
        self._fn = jax.jit(with_backend(
            lambda p, el: G.apply(p, cfg, el, quant, policy)))
        if resident:
            depth = self.residency.depth
            self._fn_packed = jax.jit(with_backend(
                lambda p, b: G.apply_packed_resident(
                    p, cfg, b, quant, policy, fusion_depth=depth,
                    edge_block=self.edge_block)))
        else:
            self._fn_packed = jax.jit(with_backend(
                lambda p, b: G.apply_packed(p, cfg, b, quant, policy)))
        with open(os.path.join(self.build_dir, "config.json"), "w") as f:
            json.dump({"name": self.name,
                       "model": dataclasses.asdict(cfg),
                       "quant": str(self.fpx),
                       "float_or_fixed": self.float_or_fixed,
                       # the resolved (possibly calibrated) per-layer
                       # precision policy this project's programs bake in
                       "precision": policy.describe(),
                       "max_nodes": self.max_nodes,
                       "max_edges": self.max_edges,
                       "batch_graphs": self.batch_graphs,
                       "node_budget": self.node_budget,
                       "edge_budget": self.edge_budget,
                       "edge_block": self.edge_block,
                       "node_block": self.node_block,
                       "agg_backend": self.agg_backend,
                       "gather_mode": self.gather_mode,
                       "fusion_depth": self.fusion_depth,
                       # the planner's verdict + whether the resident
                       # packed program actually engaged (it also needs
                       # the pallas backend and no legacy quant hook)
                       "residency": dataclasses.asdict(self.residency),
                       "residency_engaged": resident,
                       "num_shards": self.num_shards,
                       "partition": self.partition,
                       "dataflow": cfg.gnn_dataflow,
                       "dataflow_per_layer": [
                           Cv.resolve_dataflow(cfg.conv_cfg(i))
                           for i in range(cfg.gnn_num_layers)]},
                      f, indent=1, default=str)
        return self._fn

    def _abstract_graph(self):
        n, e = self.max_nodes, self.max_edges
        c = self.dataset_cfg
        sds = jax.ShapeDtypeStruct
        return {"node_feat": sds((n, c.node_feat_dim), jnp.float32),
                "edge_index": sds((e, 2), jnp.int32),
                "edge_feat": sds((e, c.edge_feat_dim), jnp.float32),
                "num_nodes": sds((), jnp.int32)}

    def _abstract_packed(self):
        nb, eb, gm = self.node_budget, self.edge_budget, self.batch_graphs
        c = self.dataset_cfg
        sds = jax.ShapeDtypeStruct
        return {"node_feat": sds((nb, c.node_feat_dim), jnp.float32),
                "node_graph_id": sds((nb,), jnp.int32),
                "edge_index": sds((eb, 2), jnp.int32),
                "edge_feat": sds((eb, c.edge_feat_dim), jnp.float32),
                "edge_graph_id": sds((eb,), jnp.int32),
                "graph_valid": sds((gm,), jnp.bool_),
                "graph_num_nodes": sds((gm,), jnp.int32),
                "num_graphs": sds((), jnp.int32)}

    _packed_to_device = staticmethod(G.packed_to_device)

    # -------------------------------------------------------- testbench --
    def gen_testbench(self, num_graphs: int = 64):
        """Export dataset graphs + float32 reference outputs (the paper's
        binary testbench data)."""
        ds = [data_mod.make_graph(self.dataset_cfg, i)
              for i in range(num_graphs)]
        if self.params is None:
            self.init_params()
        # the reference is always the full-precision program: pin an
        # explicit fp32 policy so cfg.gnn_precision cannot leak into it
        fp32 = Q.resolve_policy("fp32", self.cfg.gnn_num_layers)
        ref_fn = jax.jit(lambda p, el: G.apply(p, self.cfg, el, None, fp32))
        refs = [np.asarray(ref_fn(self.params, self._graph_to_el(g)))
                for g in ds]
        np.savez(os.path.join(self.build_dir, "testbench.npz"),
                 refs=np.stack(refs), n=num_graphs)
        self._tb_graphs = ds
        self._tb_refs = refs
        return len(ds)

    @staticmethod
    def _graph_to_el(g: data_mod.Graph) -> dict:
        return {"node_feat": jnp.asarray(g.node_feat),
                "edge_index": jnp.asarray(g.edge_index),
                "edge_feat": jnp.asarray(g.edge_feat),
                "num_nodes": jnp.int32(g.num_nodes)}

    def calibrate(self, num_graphs: int = 8):
        """Max-abs-calibrate the project's int8 grids on a packed batch
        of testbench graphs, then regenerate the jitted programs (and
        config.json) with the calibrated policy. No-op for fp32/bf16."""
        if not self.policy.needs_calibration:
            return self.policy
        if self.params is None:
            self.init_params()
        graphs = getattr(self, "_tb_graphs", None) \
            or [data_mod.make_graph(self.dataset_cfg, i)
                for i in range(num_graphs)]
        batch, _ = data_mod.pack_graphs(
            graphs[:num_graphs], self.node_budget, self.edge_budget,
            self.batch_graphs)
        self.policy = G.calibrated_policy(
            self.params, self.cfg, self._packed_to_device(batch),
            self.policy)
        self.gen_hw_model()          # re-bake programs + config.json
        return self.policy

    def build_and_run_testbench(self, packed: bool = True) -> dict:
        """Run the generated program on every testbench graph; report MAE
        vs the float reference and the measured mean runtime. With
        ``packed`` (default) the same graphs are also drained through the
        packed GraphBatch program, reporting throughput in graphs/s next
        to the single-graph latency; ``num_shards > 1`` projects
        additionally drain per-device shard waves through the sharded
        SPMD program (``tb["sharded"]``, skipped with a note when the
        host has fewer devices than shards). Quantized projects (int8 policy or
        the legacy fixed path) also report quantization-error stats
        (mean/max/SQNR-dB, ``quantization.quant_error_stats``)."""
        if self.params is None:
            self.init_params()
        if self.policy.needs_calibration:
            self.calibrate()
        if self._fn is None:
            self.gen_hw_model()
        params = self.params
        if self.float_or_fixed == "fixed":
            params = Q.quantize_tree(params, self.fpx)
        maes, times, outs = [], [], []
        out = None
        for g, ref in zip(self._tb_graphs, self._tb_refs):
            el = self._graph_to_el(g)
            out = self._fn(params, el)
            jax.block_until_ready(out)
        for g, ref in zip(self._tb_graphs, self._tb_refs):
            el = self._graph_to_el(g)
            t0 = time.perf_counter()
            out = self._fn(params, el)
            jax.block_until_ready(out)
            times.append(time.perf_counter() - t0)
            outs.append(np.asarray(out))
            maes.append(float(np.mean(np.abs(outs[-1] - ref))))
        tb = {"mae": float(np.mean(maes)),
              "mean_runtime_ms": float(np.mean(times) * 1e3),
              "p50_runtime_ms": float(np.median(times) * 1e3),
              "n_graphs": len(self._tb_graphs),
              "loop_graphs_per_s": 1.0 / max(float(np.mean(times)), 1e-12),
              "quant": str(self.fpx) if self.float_or_fixed == "fixed"
              else "float32",
              "precision": self.policy.name}
        # quant-error report next to the throughput numbers: output error
        # vs the float references, plus the weight-grid error of the
        # quantized formats (quant_error_stats reduces; callers don't)
        if not self.policy.is_fp32 or self.float_or_fixed == "fixed":
            tb["quant_error"] = {"output": Q.error_stats(
                np.stack(outs), np.stack(self._tb_refs))}
            if self.float_or_fixed == "fixed":
                leaves = np.concatenate(
                    [np.asarray(a).ravel() for a in
                     jax.tree_util.tree_leaves(self.params)])
                tb["quant_error"]["weights"] = Q.quant_error_stats(
                    leaves, self.fpx)
            elif any(lp.compute == "int8" for lp in self.policy.layers) \
                    or self.policy.head.compute == "int8":
                # the exact weight tensors the datapath quantizes, each
                # against its own calibrated grid: per-layer conv weights
                # + the head (skip projections stay fp32 in _backbone)
                orig = {f"c{i}": self.params["convs"][f"c{i}"]
                        for i in range(self.cfg.gnn_num_layers)}
                orig["mlp"] = self.params.get("mlp", {})
                cast = {f"c{i}": self.policy.layer(i).cast_params(
                    self.params["convs"][f"c{i}"])
                    for i in range(self.cfg.gnn_num_layers)}
                cast["mlp"] = self.policy.head.cast_params(orig["mlp"])
                flat = [np.concatenate(
                    [np.asarray(a).ravel() for a in
                     jax.tree_util.tree_leaves(t)]) for t in (cast, orig)]
                tb["quant_error"]["weights"] = Q.error_stats(*flat)
        if packed:
            tb["packed"] = self._run_packed_testbench(params)
            if self.num_shards > 1:
                tb["sharded"] = self._run_sharded_testbench(params)
        with open(os.path.join(self.build_dir, "tb_data.json"), "w") as f:
            json.dump(tb, f, indent=1)
        return tb

    def _run_packed_testbench(self, params) -> dict:
        """Drain the testbench graphs through the packed program and
        compare against the per-graph float references."""
        batches, dropped = data_mod.pack_dataset(
            self._tb_graphs, self.node_budget, self.edge_budget,
            self.batch_graphs)
        dev_batches = [self._packed_to_device(b) for b in batches]
        for b in dev_batches:                       # warmup / compile
            jax.block_until_ready(self._fn_packed(params, b))
        n_graphs = 0
        maes = []
        t0 = time.perf_counter()
        outs = []
        for b in dev_batches:
            outs.append(self._fn_packed(params, b))
        jax.block_until_ready(outs)
        total_s = time.perf_counter() - t0
        refs = iter(r for g, r in zip(self._tb_graphs, self._tb_refs)
                    if data_mod.graph_fits_budget(
                        g, self.node_budget, self.edge_budget))
        for b, out in zip(batches, outs):
            k = int(b["num_graphs"])
            out = np.asarray(out)
            if self.cfg.task == "graph":
                for i in range(k):
                    maes.append(float(np.mean(np.abs(out[i] - next(refs)))))
            else:    # node task: rows are packed node embeddings
                off = 0
                for i in range(k):
                    n = int(b["graph_num_nodes"][i])
                    ref = next(refs)[:n]
                    maes.append(float(np.mean(
                        np.abs(out[off:off + n] - ref))))
                    off += n
            n_graphs += k
        return {
            "mae": float(np.mean(maes)) if maes else float("nan"),
            "graphs_per_s": n_graphs / max(total_s, 1e-12),
            "mean_batch_ms": total_s / max(len(batches), 1) * 1e3,
            "n_batches": len(batches),
            "n_graphs": n_graphs,
            "n_dropped": len(dropped),
            "batch_graphs": self.batch_graphs,
            "node_budget": self.node_budget,
            "edge_budget": self.edge_budget,
        }

    def _run_sharded_testbench(self, params) -> dict:
        """Drain the testbench graphs through the data-parallel sharded
        program — one SPMD program, each device of the ("data",) mesh
        consuming its own packed shard — and report sharded graphs/s
        next to the single-device packed numbers, with MAE against the
        same per-graph float references (host order restored by
        gather_shard_outputs)."""
        if len(jax.devices()) < self.num_shards:
            return {"skipped": f"needs {self.num_shards} devices, have "
                               f"{len(jax.devices())} (set XLA_FLAGS="
                               "--xla_force_host_platform_device_count)",
                    "num_shards": self.num_shards}
        from repro.core import aggregations as agg_mod
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh(self.num_shards)
        quant = self.fpx if self.float_or_fixed == "fixed" else None
        base = G.make_sharded_apply(self.cfg, mesh, quant, self.policy)

        def fn(p, b):
            # trace-time backend scope, as gen_hw_model bakes into the
            # single-device programs
            with agg_mod.backend_scope(self.agg_backend, self.edge_block,
                                       self.node_block,
                                       gather_mode=self.gather_mode):
                return base(p, b)

        waves, dropped = data_mod.pack_dataset(
            self._tb_graphs, self.node_budget, self.edge_budget,
            self.batch_graphs, num_shards=self.num_shards)
        stacked = [G.stack_shards(w) for w in waves]
        for b in stacked:                           # warmup / compile
            jax.block_until_ready(fn(params, b))
        t0 = time.perf_counter()
        outs = [fn(params, b) for b in stacked]
        jax.block_until_ready(outs)
        total_s = time.perf_counter() - t0
        n_graphs = sum(w.n_graphs for w in waves)
        maes = []
        if self.cfg.task == "graph":
            refs = iter(r for g, r in zip(self._tb_graphs, self._tb_refs)
                        if data_mod.graph_fits_budget(
                            g, self.node_budget, self.edge_budget))
            for w, out in zip(waves, outs):
                host = data_mod.gather_shard_outputs(np.asarray(out),
                                                     w.index)
                for i in range(w.n_graphs):
                    maes.append(float(np.mean(np.abs(host[i]
                                                     - next(refs)))))
        return {
            "mae": float(np.mean(maes)) if maes else float("nan"),
            "graphs_per_s": n_graphs / max(total_s, 1e-12),
            "mean_wave_ms": total_s / max(len(waves), 1) * 1e3,
            "n_waves": len(waves),
            "n_graphs": n_graphs,
            "n_dropped": len(dropped),
            "num_shards": self.num_shards,
            "batch_graphs": self.batch_graphs,
            "node_budget": self.node_budget,
            "edge_budget": self.edge_budget,
        }

    # -------------------------------------------------------- synthesis --
    def run_synthesis(self, save_hlo: bool = False) -> dict:
        """Compile the program and emit the synthesis report: modeled
        roofline latency (Vitis latency analogue) + memory footprints
        (BRAM analogue). Also records compile wall-time — the quantity the
        paper's DSE beats by ~6 orders of magnitude."""
        if self._fn is None:
            self.gen_hw_model()
        plan = G.model_plan(self.cfg)
        t0 = time.time()
        lowered = self._fn.lower(prm.abstract(plan), self._abstract_graph())
        compiled = lowered.compile()
        compile_s = time.time() - t0
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        flops = float(cost.get("flops", 0.0))
        bytes_ = float(cost.get("bytes accessed", 0.0))
        try:
            ma = compiled.memory_analysis()
            temp = int(getattr(ma, "temp_size_in_bytes", 0))
            args = int(getattr(ma, "argument_size_in_bytes", 0))
        except Exception:
            temp = args = 0
        # utilization scaling with parallelism factors: a p=1 design issues
        # one MAC lane-group per cycle (the FPGA p=1 analogue — no MXU
        # tiling), p_h*p_out=128 fills the 128-lane systolic dimension.
        # This is the HLS II/unroll-factor effect mapped onto the MXU.
        p_eff = min(max(self.cfg.gnn_p_hidden * self.cfg.gnn_p_out, 1),
                    128) / 128
        eff_peak = self.target.peak_flops * p_eff
        # data-width scaling: cost_analysis sees the f32/fake-quant
        # emulation, so the modeled bytes shrink with the storage width —
        # the PrecisionPolicy byte width (bf16 = 2 B, int8 = 1 B), or the
        # legacy fixed-point width (<16,10> moves half of <32,16>).
        if not self.policy.is_fp32:
            width_scale = self.policy.compute_bytes / 4.0
        elif self.float_or_fixed == "fixed":
            width_scale = self.fpx.w / 32.0
        else:
            width_scale = 1.0
        bytes_eff = bytes_ * width_scale
        latency = max(flops / eff_peak, bytes_eff / self.target.hbm_bw)
        # packed-batch program: same model compiled over the GraphBatch
        # buffers; roofline latency amortizes over batch_graphs graphs.
        t0 = time.time()
        lowered_p = self._fn_packed.lower(prm.abstract(plan),
                                          self._abstract_packed())
        compiled_p = lowered_p.compile()
        compile_packed_s = time.time() - t0
        cost_p = compiled_p.cost_analysis()
        if isinstance(cost_p, (list, tuple)):
            cost_p = cost_p[0]
        flops_p = float(cost_p.get("flops", 0.0))
        bytes_p = float(cost_p.get("bytes accessed", 0.0)) * width_scale
        # aggregation-engine tile model: grid steps per conv layer, each
        # paying a fixed dispatch/DMA overhead — the II/unroll-factor
        # analogue for the tile knobs, and what the fitted DSE models
        # learn edge_block/node_block against (smaller tiles -> more
        # steps -> higher latency). The legacy one-hot kernel sweeps
        # ceil(E/EB) x ceil(N/NB) steps; the v2 DMA kernel's grid is
        # edge-tiles only (the node table is VMEM-resident).
        grid_steps = -(-self.edge_budget // self.edge_block)
        if self.gather_mode == "onehot":
            grid_steps *= -(-self.node_budget // self.node_block)
        agg_overhead_s = (self.cfg.gnn_num_layers * grid_steps
                          * self.target.kernel_step_overhead)
        # gather-stage compute honesty: XLA's cost analysis prices the
        # program it compiled, not the Pallas kernel the pallas backend
        # dispatches at run time — and the legacy one-hot kernel's dense
        # contractions are compute-bound by orders of magnitude. Fold
        # the modeled gather FLOPs (convs.gather_compute_flops) into the
        # roofline so a one-hot design can no longer "win" on modeled
        # bytes while losing 40x on the clock.
        gather_flops = 0.0
        if self.agg_backend == "pallas":
            feat = max(self.cfg.gnn_hidden_dim,
                       self.cfg.graph_input_feature_dim)
            gather_flops = self.cfg.gnn_num_layers \
                * Cv.gather_compute_flops(self.node_budget,
                                          self.edge_budget, feat,
                                          self.gather_mode,
                                          self.node_block)
        latency_p = max((flops_p + gather_flops) / eff_peak,
                        bytes_p / self.target.hbm_bw) + agg_overhead_s
        packed = {
            "latency_s": latency_p,
            "precision": self.policy.name,
            "compute_bytes": self.policy.compute_bytes,
            "agg_grid_steps": grid_steps,
            "agg_overhead_s": agg_overhead_s,
            "gather_mode": self.gather_mode,
            "gather_flops": gather_flops,
            "fusion_depth": self.fusion_depth,
            "residency_engaged": bool(
                getattr(self, "residency_engaged", False)),
            "edge_block": self.edge_block,
            "node_block": self.node_block,
            "flops": flops_p,
            "bytes_accessed": bytes_p,
            "batch_graphs": self.batch_graphs,
            "node_budget": self.node_budget,
            "edge_budget": self.edge_budget,
            "graphs_per_s": self.batch_graphs / max(latency_p, 1e-18),
            "per_graph_latency_s": latency_p / max(self.batch_graphs, 1),
            "compile_s": compile_packed_s,
        }
        # data-parallel sharded scaling model: every device runs the
        # *same* per-shard program concurrently (params replicated, no
        # inter-device traffic during the layer stack), so the wave
        # latency is the per-shard latency plus the host gather of the
        # per-device outputs over ICI — near-linear in num_shards, and
        # what benchmarks/sharded_throughput.py gates against.
        if self.cfg.task == "graph":
            out_vals = self.batch_graphs * (self.cfg.mlp_head.out_dim
                                            if self.cfg.mlp_head else 1)
        else:
            out_vals = self.node_budget * self.cfg.gnn_output_dim
        gather_bytes = 0.0 if self.num_shards == 1 \
            else self.num_shards * out_vals * 4.0
        latency_sh = latency_p + gather_bytes / self.target.link_bw
        wave_graphs = self.num_shards * self.batch_graphs
        packed["sharded"] = {
            "num_shards": self.num_shards,
            "latency_s": latency_sh,
            "gather_bytes": gather_bytes,
            "wave_graphs": wave_graphs,
            "graphs_per_s": wave_graphs / max(latency_sh, 1e-18),
            "scaling_efficiency": (wave_graphs / max(latency_sh, 1e-18))
            / max(self.num_shards * packed["graphs_per_s"], 1e-18),
        }
        # intra-graph partitioned model (giant-graph inference): one
        # graph ~partition x the per-device budget, split by edge cut;
        # every device runs the per-shard program concurrently and each
        # layer boundary all-gathers the halo rows over ICI. The modeled
        # cut is the balanced worst case — (P-1)/P of the per-device
        # edge budget crosses parts — priced by convs.halo_comm_bytes at
        # the policy's storage width. The padded-oracle baseline the
        # partitioned program retires pays the full P-times-larger
        # buffers instead (latency scales ~P with no comm term).
        feat_dim = max(self.cfg.gnn_hidden_dim,
                       self.cfg.graph_input_feature_dim)
        cut_model = (self.partition - 1) / self.partition \
            * self.edge_budget
        halo_bytes = Cv.halo_comm_bytes(cut_model, feat_dim,
                                        self.policy.compute_bytes,
                                        self.cfg.gnn_num_layers)
        comm_s = halo_bytes / self.target.link_bw
        latency_pt = latency_p + comm_s
        packed["partitioned"] = {
            "partition": self.partition,
            "modeled_cut_edges": cut_model,
            "halo_comm_bytes": halo_bytes,
            "comm_s": comm_s,
            "latency_s": latency_pt,
            # one giant graph per partitioned launch: this is the rate
            # at which oversize requests drain, vs the padded oracle's
            # ~partition-times-larger single-device program
            "oversize_graphs_per_s": 1.0 / max(latency_pt, 1e-18),
            "padded_oracle_latency_s": latency_p * self.partition,
        }
        report = {
            "packed": packed,
            "latency_s": latency,
            "latency_ms": latency * 1e3,
            "flops": flops,
            "bytes_accessed": bytes_,
            "temp_bytes": temp,
            "arg_bytes": args,
            "hbm_total_bytes": temp + args,
            "fits_hbm": (temp + args) < self.target.hbm_bytes,
            "compile_s": compile_s,
            "target": self.target.name,
            "precision": self.policy.name,
        }
        self._compiled = compiled
        if save_hlo:
            with open(os.path.join(self.build_dir, "kernel.hlo"), "w") as f:
                f.write(compiled.as_text())
        with open(os.path.join(self.build_dir, "report.json"), "w") as f:
            json.dump(report, f, indent=1)
        return report

    # paper-API alias
    run_vitis_hls_synthesis = run_synthesis
