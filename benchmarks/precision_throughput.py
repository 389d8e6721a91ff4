"""Precision-polymorphic packed inference: fp32 vs bf16 vs int8-fixed.

For each precision the same model runs the packed GraphBatch program
under its PrecisionPolicy (low-precision node/message tiles, fp32
accumulation) and is compared on three axes:

* numerics — output error vs the fp32 program (the parity pin: bf16
  must keep SQNR above 30 dB with a 1e-1 absolute ceiling at this model
  size, int8 must keep SQNR above 10 dB after max-abs calibration),
* bytes — the modeled program bytes from ``Project.run_synthesis``
  (cost_analysis scaled by the policy byte width — what the DSE
  forests price), plus the modeled graphs/s they imply,
* throughput — measured packed graphs/s on this host. On CPU the
  low-precision paths run fake-quant emulation, so the *modeled* ratio
  is the acceptance proxy (same convention as benchmarks/fused_gather);
  on a TPU the measured ratio is what matters.

  PYTHONPATH=src python benchmarks/precision_throughput.py [--smoke]
      [--convs gcn sage] [--n 64] [--batch-graphs 32]

JSON lands in benchmarks/results/precision_throughput.json; --smoke
runs the gcn point only and enforces the acceptance gates (parity at
every precision, bf16 and int8 beating fp32 on modeled bytes by the
1.5x floor).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro.configs.gnn import DATASETS
from repro.core import convs as Cv
from repro.core import gnn_model as G
from repro.core import quantization as Q
from repro.data import pipeline as P
from repro.nn import param as prm

RESULTS = os.path.join(os.path.dirname(__file__), "results")
PRECISIONS = ("fp32", "bf16", "int8")
BYTES_FLOOR = 1.5        # low precision must cut modeled bytes >= 1.5x
# model-level parity gates for this (hidden 64, 3-linear head) config:
# bf16 rounding accumulates past the reduced-config 5e-2 budget, so the
# robust gate is SQNR with a loose absolute ceiling
BF16_TOL = 1e-1          # bf16 absolute ceiling at this model size
BF16_SQNR_FLOOR = 30.0   # dB, bf16 output vs fp32
INT8_SQNR_FLOOR = 10.0   # dB, calibrated int8 output vs fp32


def _cfg(conv: str, ds) -> G.GNNModelConfig:
    return G.GNNModelConfig(
        graph_input_feature_dim=ds.node_feat_dim,
        graph_input_edge_dim=ds.edge_feat_dim,
        gnn_hidden_dim=64, gnn_num_layers=2, gnn_output_dim=32,
        gnn_conv=conv, gnn_skip_connection=True,
        avg_degree=float(ds.avg_degree),
        mlp_head=G.MLPConfig(in_dim=32 * 3, out_dim=1, hidden_dim=32,
                             hidden_layers=2))


def _modeled(conv: str, precision: str, batch_graphs: int,
             build_root: str) -> dict:
    """Project synthesis for this (conv, precision): the width-scaled
    modeled bytes + roofline graphs/s the DSE objective sees."""
    from repro.core.project import Project
    ds = DATASETS["qm9"]
    proj = Project(f"prec_{conv}_{precision}", _cfg(conv, ds), "bench",
                   os.path.join(build_root, f"{conv}_{precision}"),
                   max_nodes=ds.max_nodes, max_edges=ds.max_edges,
                   num_nodes_guess=ds.avg_nodes,
                   num_edges_guess=ds.avg_nodes * ds.avg_degree,
                   degree_guess=ds.avg_degree,
                   batch_graphs=batch_graphs, precision=precision)
    proj.gen_hw_model()
    rep = proj.run_synthesis()["packed"]
    return {"bytes": rep["bytes_accessed"],
            "graphs_per_s": rep["graphs_per_s"],
            "compute_bytes": rep["compute_bytes"]}


def run_point(conv: str, n_graphs: int, batch_graphs: int,
              repeats: int, build_root: str, log=print) -> dict:
    ds = DATASETS["qm9"]
    cfg = _cfg(conv, ds)
    params = prm.materialize(G.model_plan(cfg), jax.random.key(0))
    graphs = [P.make_graph(ds, i) for i in range(n_graphs)]
    node_budget = P.size_budget(batch_graphs, ds.avg_nodes)
    edge_budget = P.size_budget(batch_graphs,
                                ds.avg_nodes * ds.avg_degree)
    batches, _ = P.pack_dataset(graphs, node_budget, edge_budget,
                                batch_graphs)
    dev = [G.packed_to_device(b) for b in batches]
    n_packed = sum(int(b["num_graphs"]) for b in batches)

    out = {"conv": conv, "n_graphs": n_packed,
           "batch_graphs": batch_graphs, "precisions": {}}
    ref_outs = None
    for precision in PRECISIONS:
        policy = G.calibrated_policy(params, cfg, dev[0], precision)
        fn = jax.jit(lambda p, b, pol=policy: G.apply_packed(
            p, cfg, b, None, pol))
        for b in dev:                                    # compile
            jax.block_until_ready(fn(params, b))
        best = float("inf")
        outs = None
        for _ in range(repeats):
            t0 = time.perf_counter()
            outs = [fn(params, b) for b in dev]
            jax.block_until_ready(outs)
            best = min(best, time.perf_counter() - t0)
        outs = [np.asarray(o) for o in outs]
        if precision == "fp32":
            ref_outs = outs
        err = Q.error_stats(
            np.concatenate([o[:int(b["num_graphs"])] for o, b in
                            zip(outs, batches)]),
            np.concatenate([o[:int(b["num_graphs"])] for o, b in
                            zip(ref_outs, batches)]))
        rec = {"measured_graphs_per_s": n_packed / best,
               "policy": policy.describe(),
               "error_vs_fp32": err,
               "modeled": _modeled(conv, precision, batch_graphs,
                                   build_root)}
        out["precisions"][precision] = rec
        if log:
            m = rec["modeled"]
            log(f"{conv}/{precision}: {rec['measured_graphs_per_s']:8.0f}"
                f" graphs/s measured | modeled {m['graphs_per_s']:10.0f}"
                f" graphs/s, {m['bytes'] / 1e6:6.2f} MB | max err "
                f"{err['max_abs']:.2e} (SQNR {err['sqnr_db']:5.1f} dB)")
    base = out["precisions"]["fp32"]["modeled"]["bytes"]
    for precision in ("bf16", "int8"):
        rec = out["precisions"][precision]
        rec["modeled_bytes_ratio"] = base / rec["modeled"]["bytes"]
    return out


def run(convs=None, n_graphs: int = 64,
        batch_graphs: int = 32, repeats: int = 3, smoke: bool = False,
        build_root: str = "/tmp/gnnb_precision_bench",
        log=print) -> dict:
    if smoke:
        convs = ("gcn",)
    elif convs is None:
        convs = Cv.STACK_CONVS         # registry-derived: gat included
    res = {"dataset": "qm9", "n_graphs": n_graphs,
           "batch_graphs": batch_graphs,
           "jax_backend": jax.default_backend(),
           "bytes_floor": BYTES_FLOOR, "points": []}
    for conv in convs:
        res["points"].append(run_point(conv, n_graphs, batch_graphs,
                                       repeats, build_root, log=log))
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "precision_throughput.json"),
              "w") as fh:
        json.dump(res, fh, indent=1)
    return res


def check_acceptance(res: dict):
    """Parity must hold at every precision and the low-precision paths
    must beat fp32 on modeled bytes by >= 1.5x (the smoke/CI gate; on
    TPU the measured throughput would be gated instead)."""
    for pt in res["points"]:
        precs = pt["precisions"]
        bf16, int8 = precs["bf16"], precs["int8"]
        assert bf16["error_vs_fp32"]["max_abs"] < BF16_TOL, pt["conv"]
        assert bf16["error_vs_fp32"]["sqnr_db"] > BF16_SQNR_FLOOR, \
            (pt["conv"], bf16["error_vs_fp32"])
        assert int8["error_vs_fp32"]["sqnr_db"] > INT8_SQNR_FLOOR, \
            (pt["conv"], int8["error_vs_fp32"])
        for name in ("bf16", "int8"):
            ratio = precs[name]["modeled_bytes_ratio"]
            assert ratio >= BYTES_FLOOR, (pt["conv"], name, ratio)


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="gcn-only point + acceptance gates (parity per "
                         "precision, >= 1.5x modeled-bytes cut)")
    ap.add_argument("--convs", nargs="+",
                    default=list(Cv.STACK_CONVS),
                    choices=list(Cv.STACK_CONVS))
    ap.add_argument("--n", type=int, default=64)
    ap.add_argument("--batch-graphs", type=int, default=32)
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args()
    res = run(tuple(args.convs), args.n, args.batch_graphs,
              args.repeats, smoke=args.smoke)
    check_acceptance(res)
    print(f"wrote {os.path.join(RESULTS, 'precision_throughput.json')} "
          f"({res['jax_backend']} backend) — acceptance OK (parity per "
          f"precision, low-precision wins modeled bytes >= "
          f"{BYTES_FLOOR}x)")
