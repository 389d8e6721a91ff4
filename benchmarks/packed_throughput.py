"""Packed GraphBatch throughput vs the per-graph padded loop.

Acceptance benchmark for the GraphBatch IR (DESIGN_BATCHING.md): a packed
batch of >= 32 QM9-like graphs runs through ``apply_packed`` as one jitted
program and must (a) match the per-graph ``apply`` outputs within 1e-4 MAE
and (b) deliver >= 5x graphs/s over the padded per-graph loop at equal
model config. The padded loop pads every graph to max_nodes (600 for the
QM9 stand-in) — the ~97% node-slot waste this refactor removes.

Sweeps all four paper convs by default and, per conv, also times the
fused gather->aggregate path (``aggregations.backend_scope("pallas")``,
which lowers the linear convs through ``kernels/fused_gather_aggregate``)
next to the unfused XLA path — the per-conv fused/unfused graphs/s pairs
seed the perf trajectory in the results JSON. On non-TPU hosts the fused
program runs the kernels in interpret mode; the number is recorded
either way (flagged ``fused_mode``).

  PYTHONPATH=src python benchmarks/packed_throughput.py [--n 64] \
      [--batch-graphs 32] [--convs gcn sage gin pna] [--no-fused]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.gnn import DATASETS, benchmark_config
from repro.core import convs as Cv
from repro.core import aggregations as agg_mod
from repro.core import gnn_model as G
from repro.data import pipeline as P
from repro.nn import param as prm

RESULTS = os.path.join(os.path.dirname(__file__), "results")


def run(conv: str = "gcn", dataset: str = "qm9", n_graphs: int = 64,
        batch_graphs: int = 32, repeats: int = 3, fused: bool = False,
        log=print) -> dict:
    cfg = benchmark_config(conv, dataset, parallel=True)
    ds = DATASETS[dataset]
    params = prm.materialize(G.model_plan(cfg), jax.random.key(0))
    graphs = [P.make_graph(ds, i) for i in range(n_graphs)]

    # --- per-graph padded loop (the seed's execution model) -------------
    loop_fn = jax.jit(lambda p, el: G.apply(p, cfg, el))
    els = [{"node_feat": jnp.asarray(g.node_feat),
            "edge_index": jnp.asarray(g.edge_index),
            "edge_feat": jnp.asarray(g.edge_feat),
            "num_nodes": jnp.int32(g.num_nodes)} for g in graphs]
    jax.block_until_ready(loop_fn(params, els[0]))        # compile
    loop_s = []
    refs = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        outs = [loop_fn(params, el) for el in els]
        jax.block_until_ready(outs)
        loop_s.append(time.perf_counter() - t0)
        refs = [np.asarray(o) for o in outs]
    loop_gps = n_graphs / min(loop_s)

    # --- packed GraphBatch path ----------------------------------------
    node_budget = P.size_budget(batch_graphs, ds.avg_nodes)
    edge_budget = P.size_budget(batch_graphs,
                                ds.avg_nodes * ds.avg_degree)
    batches, dropped = P.pack_dataset(graphs, node_budget, edge_budget,
                                      batch_graphs)
    packed_fn = jax.jit(lambda p, b: G.apply_packed(p, cfg, b))
    dev = [G.packed_to_device(b) for b in batches]
    for b in dev:                                         # compile
        jax.block_until_ready(packed_fn(params, b))
    packed_s = []
    packed_outs = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        outs = [packed_fn(params, b) for b in dev]
        jax.block_until_ready(outs)
        packed_s.append(time.perf_counter() - t0)
        packed_outs = [np.asarray(o) for o in outs]
    n_packed = sum(int(b["num_graphs"]) for b in batches)
    packed_gps = n_packed / min(packed_s)

    # --- fused gather->aggregate path (Pallas backend) ------------------
    fused_gps = fused_mode = None
    if fused:
        on_tpu = jax.default_backend() == "tpu"
        fused_mode = "compiled" if on_tpu else "interpret"
        with agg_mod.backend_scope("pallas"):
            fused_fn = jax.jit(lambda p, b: G.apply_packed(p, cfg, b))
            jax.block_until_ready(fused_fn(params, dev[0]))  # compile
            fused_t = []
            for _ in range(repeats):
                t0 = time.perf_counter()
                outs = [fused_fn(params, b) for b in dev]
                jax.block_until_ready(outs)
                fused_t.append(time.perf_counter() - t0)
        fused_gps = n_packed / min(fused_t)

    # --- equivalence ----------------------------------------------------
    ref_iter = iter(r for g, r in zip(graphs, refs)
                    if P.graph_fits_budget(g, node_budget, edge_budget))
    maes = []
    for b, out in zip(batches, packed_outs):
        for i in range(int(b["num_graphs"])):
            maes.append(float(np.mean(np.abs(out[i] - next(ref_iter)))))
    mae = float(np.mean(maes))

    res = {
        "conv": conv, "dataset": dataset, "n_graphs": n_graphs,
        "batch_graphs": batch_graphs,
        "node_budget": node_budget, "edge_budget": edge_budget,
        "n_batches": len(batches), "n_dropped": len(dropped),
        "loop_graphs_per_s": loop_gps,
        "packed_graphs_per_s": packed_gps,
        "unfused_graphs_per_s": packed_gps,
        "fused_graphs_per_s": fused_gps,
        "fused_mode": fused_mode,
        "speedup": packed_gps / loop_gps,
        "mae_vs_loop": mae,
        "padded_node_slots": n_graphs * ds.max_nodes,
        "packed_node_slots": len(batches) * node_budget,
    }
    if log:
        fused_txt = "" if fused_gps is None else \
            f", fused {fused_gps:.0f} graphs/s ({fused_mode})"
        log(f"{conv}/{dataset}: loop {loop_gps:.0f} graphs/s, packed "
            f"{packed_gps:.0f} graphs/s ({res['speedup']:.1f}x)"
            f"{fused_txt}, MAE {mae:.2e}, slots "
            f"{res['packed_node_slots']} vs "
            f"{res['padded_node_slots']} padded")
    return res


def run_all(convs=None, dataset: str = "qm9",
            n_graphs: int = 64, batch_graphs: int = 32, repeats: int = 3,
            fused: bool = True, log=print) -> dict:
    """Sweep every conv and record per-conv fused/unfused graphs/s —
    the perf-trajectory seed for the fused edge pipeline."""
    if convs is None:
        convs = Cv.CONV_TYPES          # registry-derived: gat included
    res = {"dataset": dataset, "n_graphs": n_graphs,
           "batch_graphs": batch_graphs,
           "jax_backend": jax.default_backend(), "convs": {}}
    for conv in convs:
        res["convs"][conv] = run(conv, dataset, n_graphs, batch_graphs,
                                 repeats, fused=fused, log=log)
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "packed_throughput.json"), "w") as f:
        json.dump(res, f, indent=1)
    return res


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--convs", nargs="+",
                    default=list(Cv.CONV_TYPES),
                    choices=list(Cv.CONV_TYPES))
    ap.add_argument("--dataset", default="qm9")
    ap.add_argument("--n", type=int, default=64)
    ap.add_argument("--batch-graphs", type=int, default=32)
    ap.add_argument("--no-fused", action="store_true",
                    help="skip the Pallas fused-path timing (slow off-TPU)")
    args = ap.parse_args()
    res = run_all(tuple(args.convs), args.dataset, args.n,
                  args.batch_graphs, fused=not args.no_fused)
    for conv, r in res["convs"].items():
        assert r["mae_vs_loop"] < 1e-4, (conv, r["mae_vs_loop"])
        assert r["speedup"] >= 5.0, (conv, r["speedup"])
    print("acceptance: OK (>=5x, MAE < 1e-4, all convs)")
