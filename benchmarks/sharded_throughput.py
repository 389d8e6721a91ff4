"""Sharded packed GNN inference: throughput scaling across a device mesh.

Sweeps 1/2/4/8 data-parallel device shards. The device count must be
fixed before jax initializes, so the parent process spawns one worker
subprocess per point with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (simulated host
devices — the same mechanism the distributed tests use; on a real TPU
host the flag is unnecessary). Each worker:

* partitions the request stream into per-device shard waves
  (``pack_dataset(num_shards=)``) and runs them through the SPMD
  program from ``gnn_model.make_sharded_apply``,
* checks parity: the sharded outputs must match the single-device
  packed program shard by shard (PARITY_TOL),
* measures wave graphs/s on this host, and records the *modeled*
  sharded graphs/s from ``Project.run_synthesis`` — on CPU the
  simulated devices time-slice one socket, so the modeled figure is
  the acceptance proxy (same convention as benchmarks/fused_gather).

The parent gates near-linear modeled scaling: graphs/s at N shards must
reach ``SCALING_FLOOR * N`` times the single-device figure. JSON lands
in benchmarks/results/sharded_throughput.json.

  PYTHONPATH=src python benchmarks/sharded_throughput.py [--smoke]
      [--shards 1 2 4 8] [--n 128] [--batch-graphs 16]

``--smoke`` sweeps {1, 2} shards and enforces the parity +
modeled-scaling gates (the CI step).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

RESULTS = os.path.join(os.path.dirname(__file__), "results")
PARITY_TOL = 1e-4        # sharded vs single-device packed outputs
SCALING_FLOOR = 0.8      # modeled graphs/s at N shards >= 0.8 * N * 1-shard
MARK = "SHARDED_POINT_JSON:"


def _cfg():
    from repro.configs.gnn import DATASETS
    from repro.core import gnn_model as G
    ds = DATASETS["qm9"]
    return ds, G.GNNModelConfig(
        graph_input_feature_dim=ds.node_feat_dim,
        graph_input_edge_dim=ds.edge_feat_dim,
        gnn_hidden_dim=64, gnn_num_layers=2, gnn_output_dim=32,
        gnn_conv="gcn", gnn_skip_connection=True,
        avg_degree=float(ds.avg_degree),
        mlp_head=G.MLPConfig(in_dim=32 * 3, out_dim=1, hidden_dim=32,
                             hidden_layers=2))


def worker(num_shards: int, n_graphs: int, batch_graphs: int,
           repeats: int) -> dict:
    """Runs inside the subprocess whose XLA_FLAGS pinned the device
    count; measures + models one shard-count point and prints it as a
    single marked JSON line for the parent to collect."""
    import jax
    import numpy as np

    from repro.core import gnn_model as G
    from repro.core.project import Project
    from repro.data import pipeline as P
    from repro.launch.mesh import make_data_mesh
    from repro.nn import param as prm

    ds, cfg = _cfg()
    params = prm.materialize(G.model_plan(cfg), jax.random.key(0))
    graphs = [P.make_graph(ds, i) for i in range(n_graphs)]
    node_budget = P.size_budget(batch_graphs, ds.avg_nodes)
    edge_budget = P.size_budget(batch_graphs,
                                ds.avg_nodes * ds.avg_degree)
    waves, dropped = P.pack_dataset(graphs, node_budget, edge_budget,
                                    batch_graphs, num_shards=num_shards)
    if num_shards == 1:
        waves = [P.ShardedBatch([b], [list(range(int(b["num_graphs"])))])
                 for b in waves]
    mesh = make_data_mesh(num_shards)
    fn = G.make_sharded_apply(cfg, mesh)
    single_fn = jax.jit(lambda p, b: G.apply_packed(p, cfg, b))

    # parity: each shard of the first wave vs the single-device program
    stacked0 = G.stack_shards(waves[0], mesh)
    out0 = np.asarray(fn(params, stacked0))
    max_err = 0.0
    for s, shard in enumerate(waves[0].shards):
        ref = np.asarray(single_fn(params, G.packed_to_device(shard)))
        max_err = max(max_err, float(np.abs(out0[s] - ref).max()))

    stacked = [G.stack_shards(w, mesh) for w in waves]
    for b in stacked:                                   # compile/warmup
        jax.block_until_ready(fn(params, b))
    n_served = sum(w.n_graphs for w in waves)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        outs = [fn(params, b) for b in stacked]
        jax.block_until_ready(outs)
        best = min(best, time.perf_counter() - t0)

    proj = Project(f"sharded_{num_shards}", cfg, "bench",
                   f"/tmp/gnnb_sharded_bench/{num_shards}",
                   max_nodes=ds.max_nodes, max_edges=ds.max_edges,
                   num_nodes_guess=ds.avg_nodes,
                   num_edges_guess=ds.avg_nodes * ds.avg_degree,
                   degree_guess=ds.avg_degree,
                   batch_graphs=batch_graphs, num_shards=num_shards)
    proj.gen_hw_model()
    modeled = proj.run_synthesis()["packed"]["sharded"]

    return {"num_shards": num_shards,
            "devices": len(jax.devices()),
            "n_graphs": n_served,
            "n_waves": len(waves),
            "n_dropped": len(dropped),
            "parity_max_err": max_err,
            "measured_graphs_per_s": n_served / max(best, 1e-12),
            "modeled_graphs_per_s": modeled["graphs_per_s"],
            "modeled_latency_s": modeled["latency_s"],
            "scaling_efficiency": modeled["scaling_efficiency"]}


def sweep(shard_counts, n_graphs: int, batch_graphs: int, repeats: int,
          log=print) -> dict:
    """Parent: one subprocess per shard count, XLA_FLAGS pinned."""
    points = []
    for n in shard_counts:
        env = dict(os.environ)
        flags = env.get("XLA_FLAGS", "")
        flags = " ".join(f for f in flags.split()
                         if "xla_force_host_platform_device_count"
                         not in f)
        env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_"
                            f"device_count={n}").strip()
        env["PYTHONPATH"] = os.path.join(
            os.path.dirname(__file__), "..", "src") \
            + os.pathsep + env.get("PYTHONPATH", "")
        cmd = [sys.executable, os.path.abspath(__file__), "--worker",
               str(n), "--n", str(n_graphs),
               "--batch-graphs", str(batch_graphs),
               "--repeats", str(repeats)]
        out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                             timeout=900)
        line = next((ln for ln in out.stdout.splitlines()
                     if ln.startswith(MARK)), None)
        if line is None:
            raise RuntimeError(
                f"worker for {n} shards produced no result:\n"
                f"{out.stdout[-2000:]}\n{out.stderr[-2000:]}")
        pt = json.loads(line[len(MARK):])
        points.append(pt)
        if log:
            log(f"shards={n}: modeled {pt['modeled_graphs_per_s']:12.0f} "
                f"graphs/s ({pt['scaling_efficiency'] * 100:5.1f}% "
                f"scaling eff) | measured "
                f"{pt['measured_graphs_per_s']:8.0f} graphs/s "
                f"(simulated devices) | parity max err "
                f"{pt['parity_max_err']:.2e}")
    return {"dataset": "qm9", "conv": "gcn", "n_graphs": n_graphs,
            "batch_graphs": batch_graphs,
            "parity_tol": PARITY_TOL, "scaling_floor": SCALING_FLOOR,
            "points": points}


def check_acceptance(res: dict):
    """Parity at every shard count; modeled graphs/s must scale
    near-linearly (>= SCALING_FLOOR * N vs the 1-shard point)."""
    pts = {p["num_shards"]: p for p in res["points"]}
    for n, p in pts.items():
        assert p["parity_max_err"] < PARITY_TOL, (n, p["parity_max_err"])
    base = pts[1]["modeled_graphs_per_s"]
    for n, p in pts.items():
        ratio = p["modeled_graphs_per_s"] / base
        assert ratio >= SCALING_FLOOR * n, (n, ratio)


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", type=int, default=None,
                    help=argparse.SUPPRESS)   # internal: one sweep point
    ap.add_argument("--smoke", action="store_true",
                    help="{1,2}-shard sweep + parity/scaling gates "
                         "(the CI step)")
    ap.add_argument("--shards", type=int, nargs="+",
                    default=[1, 2, 4, 8])
    ap.add_argument("--n", type=int, default=128)
    ap.add_argument("--batch-graphs", type=int, default=16)
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args()

    if args.worker is not None:
        pt = worker(args.worker, args.n, args.batch_graphs, args.repeats)
        print(MARK + json.dumps(pt))
        sys.exit(0)

    counts = [1, 2] if args.smoke else args.shards
    if 1 not in counts:
        counts = [1] + counts                 # scaling baseline
    res = sweep(counts, args.n, args.batch_graphs, args.repeats)
    os.makedirs(RESULTS, exist_ok=True)
    path = os.path.join(RESULTS, "sharded_throughput.json")
    with open(path, "w") as fh:
        json.dump(res, fh, indent=1)
    check_acceptance(res)
    print(f"wrote {path} — acceptance OK (parity < {PARITY_TOL} at every "
          f"shard count, modeled scaling >= {SCALING_FLOOR}x linear)")
