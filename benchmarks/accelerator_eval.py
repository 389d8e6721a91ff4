"""Paper Table IV + Fig. 6: generated-accelerator speedup evaluation.

Implementations compared (batch=1 per-graph latency, as in the paper):
  jax-cpu  — PyG-CPU analogue: jitted XLA float32 segment-op model,
             measured on this host CPU.
  np-cpu   — C++-CPU analogue: pure-NumPy forward (no XLA), measured.
  tpu-base — FPGA-Base analogue: generated program, parallelism 1,
             <32,16> fixed point; latency = modeled roofline of the
             compiled artifact (the paper likewise reports the
             post-synthesis worst-case estimate, not silicon).
  tpu-par  — FPGA-Parallel analogue: p_hidden=16/p_out=8 (PNA 8/8),
             <16,10>; modeled likewise.

Grid: conv in {gcn, gin, pna, sage} x five MoleculeNet-statistics
datasets. Reported: per-conv speedups of tpu-par over each baseline +
geometric means (paper: 6.33x PyG-CPU, 6.87x PyG-GPU, 7.08x C++-CPU).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.gnn import DATASETS, FPX_BASE, FPX_PARALLEL, \
    benchmark_config
from repro.core import gnn_model as G
from repro.core.project import Project
from repro.data.pipeline import make_graph
from repro.nn import param as prm

RESULTS = os.path.join(os.path.dirname(__file__), "results")
CONVS = ("gcn", "gin", "pna", "sage")


# ------------------------------------------------- numpy (cpp) baseline --
def _np_linear(p, x):
    y = x @ np.asarray(p["w"], np.float32)
    if "b" in p:
        y = y + np.asarray(p["b"], np.float32)
    return y


def numpy_forward(params, cfg, g) -> np.ndarray:
    """Pure-NumPy reference forward (the C++ float CPU analogue)."""
    relu = lambda a: np.maximum(a, 0.0)
    x = g.node_feat.copy()
    n = g.num_nodes
    ei = g.edge_index[:g.num_edges]
    src, dst = ei[:, 0], ei[:, 1]
    indeg = np.bincount(dst, minlength=cfg_max_nodes(cfg, g)) \
        .astype(np.float32)
    for i in range(cfg.gnn_num_layers):
        cc = cfg.conv_cfg(i)
        pc = params["convs"][f"c{i}"]
        if cfg.gnn_conv == "gcn":
            inv = 1.0 / np.sqrt(np.maximum(indeg + 1.0, 1e-12))
            msg = (x * inv[:, None])[src]
            agg = np.zeros_like(x)
            np.add.at(agg, dst, msg)
            agg = (agg + x * inv[:, None]) * inv[:, None]
            h = _np_linear(pc["w"], agg)
        elif cfg.gnn_conv == "sage":
            agg = np.zeros_like(x)
            cnt = np.zeros((x.shape[0], 1), np.float32)
            np.add.at(agg, dst, x[src])
            np.add.at(cnt, dst, 1.0)
            agg = agg / np.maximum(cnt, 1.0)
            h = _np_linear(pc["w_self"], x) + _np_linear(pc["w_neigh"], agg)
        elif cfg.gnn_conv == "gin":
            msg = x[src]
            if "w_edge" in pc:
                msg = relu(msg + _np_linear(pc["w_edge"],
                                            g.edge_feat[:g.num_edges]))
            agg = np.zeros_like(x)
            np.add.at(agg, dst, msg)
            eps = float(np.asarray(pc["eps"]))
            h = _np_linear(pc["mlp2"],
                           relu(_np_linear(pc["mlp1"], (1 + eps) * x + agg)))
        else:  # pna
            feats = [x[dst], x[src], g.edge_feat[:g.num_edges].repeat(1, 0)
                     if False else g.edge_feat[:g.num_edges]]
            msg = relu(_np_linear(pc["pre"], np.concatenate(
                [x[dst], x[src], g.edge_feat[:g.num_edges]], axis=-1)))
            s = np.zeros_like(x[:, :msg.shape[1]])
            c = np.zeros((x.shape[0], 1), np.float32)
            mn = np.full_like(s, np.inf)
            mx = np.full_like(s, -np.inf)
            s2 = np.zeros_like(s)
            np.add.at(s, dst, msg)
            np.add.at(s2, dst, msg ** 2)
            np.add.at(c, dst, 1.0)
            np.minimum.at(mn, dst, msg)
            np.maximum.at(mx, dst, msg)
            cc_ = np.maximum(c, 1.0)
            mean = s / cc_
            # stable two-pass-equivalent std
            var = np.maximum(s2 / cc_ - mean ** 2, 1e-12)
            std = np.sqrt(var)
            mn = np.where(np.isfinite(mn), mn, 0.0)
            mx = np.where(np.isfinite(mx), mx, 0.0)
            logd = np.log(np.maximum(indeg, 1.0) + 1.0)[:, None]
            towers = []
            for t in (mean, mn, mx, std):
                towers += [t, t * (logd / cfg.pna_delta),
                           t * (cfg.pna_delta / logd)]
            h = _np_linear(pc["post"],
                           np.concatenate([x] + towers, axis=-1))
        if cfg.gnn_skip_connection:
            skip = x
            if f"skip{i}" in params:
                skip = _np_linear(params[f"skip{i}"], x)
            h = h + skip
        x = relu(h)
        mask = (np.arange(x.shape[0]) < n)[:, None]
        x = x * mask
    pooled = np.concatenate([
        x[:n].sum(0), x[:n].mean(0), x[:n].max(0)])
    h = pooled
    mcfg = cfg.mlp_head
    for i in range(mcfg.hidden_layers + 1):
        h = _np_linear(params["mlp"][f"l{i}"], h)
        if i < mcfg.hidden_layers:
            h = relu(h)
    return h


def cfg_max_nodes(cfg, g):
    return g.node_feat.shape[0]


# ----------------------------------------------------------- evaluation --
def run(n_graphs: int = 32, datasets=None, log=print) -> dict:
    os.makedirs(RESULTS, exist_ok=True)
    datasets = datasets or list(DATASETS)
    rows = []
    for conv in CONVS:
        for ds_name in datasets:
            ds_cfg = DATASETS[ds_name]
            cfg_par = benchmark_config(conv, ds_name, parallel=True)
            cfg_base = benchmark_config(conv, ds_name, parallel=False)
            plan = G.model_plan(cfg_par)
            params = prm.materialize(plan, jax.random.key(0))
            np_params = jax.tree_util.tree_map(
                lambda a: np.asarray(a, np.float32), params)
            graphs = [make_graph(ds_cfg, i) for i in range(n_graphs)]

            # jax-cpu measured (PyG-CPU analogue)
            fn = jax.jit(lambda p, el: G.apply(p, cfg_par, el, None))
            els = [{"node_feat": jnp.asarray(g.node_feat),
                    "edge_index": jnp.asarray(g.edge_index),
                    "edge_feat": jnp.asarray(g.edge_feat),
                    "num_nodes": jnp.int32(g.num_nodes)} for g in graphs]
            jax.block_until_ready(fn(params, els[0]))
            t0 = time.perf_counter()
            for el in els:
                out = fn(params, el)
            jax.block_until_ready(out)
            jax_ms = (time.perf_counter() - t0) / n_graphs * 1e3

            # numpy measured (C++-CPU analogue)
            t0 = time.perf_counter()
            for g in graphs:
                numpy_forward(np_params, cfg_par, g)
            np_ms = (time.perf_counter() - t0) / n_graphs * 1e3

            # generated accelerators: modeled roofline latency
            lat = {}
            for tag, mcfg, fpx in (("tpu-base", cfg_base, FPX_BASE),
                                   ("tpu-par", cfg_par, FPX_PARALLEL)):
                proj = Project(f"bench_{conv}_{ds_name}_{tag}", mcfg,
                               "bench", f"/tmp/gnnb_bench/{tag}",
                               dataset_cfg=ds_cfg, float_or_fixed="fixed",
                               fpx=fpx)
                proj.gen_hw_model()
                rep = proj.run_synthesis()
                lat[tag] = rep["latency_ms"]

            rows.append({
                "conv": conv, "dataset": ds_name,
                "jax_cpu_ms": jax_ms, "np_cpu_ms": np_ms,
                "tpu_base_ms": lat["tpu-base"],
                "tpu_par_ms": lat["tpu-par"],
            })
            if log:
                log(f"  {conv}/{ds_name}: jax {jax_ms:.2f}ms "
                    f"np {np_ms:.2f}ms base {lat['tpu-base']:.4f}ms "
                    f"par {lat['tpu-par']:.4f}ms")

    # per-conv + overall geomean speedups of tpu-par
    def geomean(v):
        return float(np.exp(np.mean(np.log(np.maximum(v, 1e-12)))))

    summary = {}
    for conv in CONVS:
        sub = [r for r in rows if r["conv"] == conv]
        summary[conv] = {
            "vs_jax_cpu": geomean(np.array(
                [r["jax_cpu_ms"] / r["tpu_par_ms"] for r in sub])),
            "vs_np_cpu": geomean(np.array(
                [r["np_cpu_ms"] / r["tpu_par_ms"] for r in sub])),
            "vs_tpu_base": geomean(np.array(
                [r["tpu_base_ms"] / r["tpu_par_ms"] for r in sub])),
        }
    summary["geomean"] = {
        k: geomean(np.array([summary[c][k] for c in CONVS]))
        for k in ("vs_jax_cpu", "vs_np_cpu", "vs_tpu_base")}
    res = {"rows": rows, "speedups": summary,
           "paper": {"vs_pyg_cpu": 6.33, "vs_pyg_gpu": 6.87,
                     "vs_cpp_cpu": 7.08}}
    with open(os.path.join(RESULTS, "accelerator_eval.json"), "w") as f:
        json.dump(res, f, indent=1)
    if log:
        log(f"geomean speedups (tpu-par): {summary['geomean']}")
    return res


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=32)
    ap.add_argument("--datasets", nargs="*", default=None)
    args = ap.parse_args()
    run(args.n, args.datasets)
