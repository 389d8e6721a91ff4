"""Giant-graph partitioned inference: padded oracle vs edge-cut sharding.

One oversize request graph — far beyond the packed per-shard budgets —
served two ways:

* **padded oracle**: the single-device program over the dataset's
  worst-case (max_nodes, max_edges) buffers, the path PR 9 retires for
  oversize traffic. It pays for every padding row on every request.
* **partitioned**: ``pipeline.partition_graph`` splits the graph across
  N devices under tight per-device budgets (BFS-front greedy edge cut +
  halo), and ``gnn_model.make_partitioned_apply`` runs the SPMD conv
  stack with per-layer halo exchange plus the single-device reassembly
  tail. Outputs must match the oracle **bitwise** at fp32.

The device count must be fixed before jax initializes, so the parent
spawns one worker subprocess per point with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the
benchmarks/sharded_throughput.py mechanism). Each worker probes the
tightest per-device node budget the partitioner fits in — what a
deployment with N devices would size its giant-graph lane at — then
measures both paths and records the modeled comm cost
(``Project.run_synthesis`` ``packed["partitioned"]``, balanced
worst-case cut) next to the measured edge-cut exchange volume
(``GraphPartition.comm_bytes``).

Simulated host devices time-slice one socket, so the measured speedup
comes from retiring the padded program's dead rows (max_nodes vs the
request's actual size), not from N-way parallel conv compute — the
parallel term is what the modeled figures carry (same convention as
benchmarks/sharded_throughput.py). The acceptance gates are bitwise
parity at every device count and >= SPEEDUP_FLOOR measured speedup at
4 devices. JSON lands in benchmarks/results/partitioned_inference.json.

  PYTHONPATH=src python benchmarks/partitioned_inference.py [--smoke]
      [--devices 2 4 8] [--repeats 20]

``--smoke`` sweeps {2, 4} devices and enforces both gates (the CI
benchmark-smoke step).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

RESULTS = os.path.join(os.path.dirname(__file__), "results")
SPEEDUP_FLOOR = 2.0      # measured padded/partitioned at 4 devices
GATE_DEVICES = 4         # the sweep point the speedup gate reads
MARK = "PARTITIONED_POINT_JSON:"

# heavy-tailed giant-graph traffic: the dataset's declared worst case
# (what the padded oracle must size its buffers for) is ~30x the
# typical oversize request the sweep serves
AVG_NODES = 600
MAX_NODES = 20000
MAX_EDGES = 24000
SEED = 17


def _cfg():
    from repro.core import gnn_model as G
    from repro.data.pipeline import GraphDataConfig
    ds = GraphDataConfig(num_graphs=1, avg_nodes=AVG_NODES, avg_degree=2,
                         node_feat_dim=11, edge_feat_dim=4, num_targets=1,
                         max_nodes=MAX_NODES, max_edges=MAX_EDGES,
                         seed=SEED)
    return ds, G.GNNModelConfig(
        graph_input_feature_dim=ds.node_feat_dim,
        graph_input_edge_dim=ds.edge_feat_dim,
        gnn_hidden_dim=128, gnn_num_layers=3, gnn_output_dim=64,
        gnn_conv="gcn", gnn_skip_connection=True,
        avg_degree=float(ds.avg_degree),
        mlp_head=G.MLPConfig(in_dim=64 * 3, out_dim=1, hidden_dim=64,
                             hidden_layers=2))


def _tight_budget(g, num_parts: int):
    """The smallest per-device node budget (16-row granularity) the
    partitioner fits this graph in at this device count."""
    from repro.data import pipeline as P
    lo = -(-int(g.num_nodes) // num_parts) + 8
    for nb in range(lo, MAX_NODES, 16):
        try:
            return nb, P.partition_graph(g, num_parts, nb, 4 * nb)
        except ValueError:
            continue
    raise RuntimeError(f"graph does not partition into {num_parts} parts")


def worker(num_devices: int, repeats: int) -> dict:
    """Runs inside the subprocess whose XLA_FLAGS pinned the device
    count; measures + models one sweep point and prints it as a single
    marked JSON line for the parent to collect."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import convs as Cv
    from repro.core import gnn_model as G
    from repro.core.project import Project
    from repro.data import pipeline as P
    from repro.launch.mesh import make_data_mesh
    from repro.nn import param as prm

    ds, cfg = _cfg()
    g = P.make_graph(ds, 0)
    params = prm.materialize(G.model_plan(cfg), jax.random.key(0))
    nb, part = _tight_budget(g, num_devices)
    mesh = make_data_mesh(num_devices)
    fn = G.make_partitioned_apply(cfg, mesh, None, None,
                                  out_rows=part.padded_nodes)
    stacked = G.stack_shards(part.parts)
    el = {"node_feat": jnp.asarray(g.node_feat),
          "edge_index": jnp.asarray(g.edge_index),
          "edge_feat": jnp.asarray(g.edge_feat),
          "num_nodes": jnp.int32(g.num_nodes)}
    padded_fn = jax.jit(lambda p, e: G.apply(p, cfg, e))

    out_part = np.asarray(fn(params, stacked))           # also warmup
    out_pad = np.asarray(padded_fn(params, el))
    bitwise = bool(np.array_equal(out_part, out_pad))
    max_err = float(np.abs(out_part - out_pad).max())

    def bench(f):
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(f())
            best = min(best, time.perf_counter() - t0)
        return best

    # two alternating passes per path: a transient load spike during one
    # pass cannot skew the ratio (best-of across both passes)
    t_part = t_pad = float("inf")
    for _ in range(2):
        t_part = min(t_part, bench(lambda: fn(params, stacked)))
        t_pad = min(t_pad, bench(lambda: padded_fn(params, el)))

    # modeled comm cost (balanced worst-case cut through the Project
    # report) vs the measured cut's exchange volume
    proj = Project(f"partitioned_{num_devices}", cfg, "bench",
                   f"/tmp/gnnb_partitioned_bench/{num_devices}",
                   max_nodes=ds.max_nodes, max_edges=ds.max_edges,
                   num_nodes_guess=ds.avg_nodes,
                   num_edges_guess=ds.avg_nodes * ds.avg_degree,
                   degree_guess=ds.avg_degree, batch_graphs=1,
                   node_budget=nb, edge_budget=4 * nb,
                   partition=num_devices)
    proj.gen_hw_model()
    modeled = proj.run_synthesis()["packed"]["partitioned"]
    measured_comm = part.comm_bytes(cfg.gnn_hidden_dim, 4.0,
                                    cfg.gnn_num_layers)

    return {"num_devices": num_devices,
            "devices": len(jax.devices()),
            "graph_nodes": int(g.num_nodes),
            "graph_edges": int(g.num_edges),
            "padded_rows": int(g.node_feat.shape[0]),
            "node_budget": nb,
            "edge_budget": 4 * nb,
            "cut_edges": int(part.cut_edges),
            "halo_nodes": int(part.halo_nodes),
            "bitwise": bitwise,
            "max_err": max_err,
            "partitioned_ms": t_part * 1e3,
            "padded_ms": t_pad * 1e3,
            "speedup": t_pad / max(t_part, 1e-12),
            "measured_comm_bytes": measured_comm,
            "modeled_comm_bytes": modeled["halo_comm_bytes"],
            "modeled_cut_edges": modeled["modeled_cut_edges"],
            "modeled_latency_s": modeled["latency_s"],
            "modeled_padded_latency_s": modeled["padded_oracle_latency_s"]}


def sweep(device_counts, repeats: int, log=print) -> dict:
    """Parent: one subprocess per device count, XLA_FLAGS pinned."""
    points = []
    for n in device_counts:
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
               str(n), "--repeats", str(repeats)]
        out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                             timeout=900)
        line = next((ln for ln in out.stdout.splitlines()
                     if ln.startswith(MARK)), None)
        if line is None:
            raise RuntimeError(
                f"worker for {n} devices produced no result:\n"
                f"{out.stdout[-2000:]}\n{out.stderr[-2000:]}")
        pt = json.loads(line[len(MARK):])
        points.append(pt)
        if log:
            log(f"devices={n}: partitioned {pt['partitioned_ms']:7.2f} ms "
                f"vs padded {pt['padded_ms']:7.2f} ms "
                f"({pt['speedup']:.2f}x, bitwise={pt['bitwise']}) | "
                f"cut {pt['cut_edges']} edges, exchange "
                f"{pt['measured_comm_bytes'] / 1e3:.0f} kB measured / "
                f"{pt['modeled_comm_bytes'] / 1e3:.0f} kB modeled")
    return {"avg_nodes": AVG_NODES, "max_nodes": MAX_NODES,
            "max_edges": MAX_EDGES, "conv": "gcn", "precision": "fp32",
            "speedup_floor": SPEEDUP_FLOOR, "gate_devices": GATE_DEVICES,
            "points": points}


def check_acceptance(res: dict):
    """Bitwise fp32 parity at every device count; measured speedup over
    the padded oracle >= SPEEDUP_FLOOR at GATE_DEVICES devices."""
    pts = {p["num_devices"]: p for p in res["points"]}
    for n, p in pts.items():
        assert p["bitwise"], (n, p["max_err"])
    gate = pts.get(GATE_DEVICES)
    assert gate is not None, f"sweep has no {GATE_DEVICES}-device point"
    assert gate["speedup"] >= SPEEDUP_FLOOR, \
        (gate["speedup"], SPEEDUP_FLOOR)


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", type=int, default=None,
                    help=argparse.SUPPRESS)   # internal: one sweep point
    ap.add_argument("--smoke", action="store_true",
                    help="{2,4}-device sweep + parity/speedup gates "
                         "(the CI step)")
    ap.add_argument("--devices", type=int, nargs="+", default=[2, 4, 8])
    ap.add_argument("--repeats", type=int, default=20)
    args = ap.parse_args()

    if args.worker is not None:
        pt = worker(args.worker, args.repeats)
        print(MARK + json.dumps(pt))
        sys.exit(0)

    counts = [2, 4] if args.smoke else args.devices
    if GATE_DEVICES not in counts:
        counts = sorted(set(counts) | {GATE_DEVICES})
    res = sweep(counts, args.repeats)
    os.makedirs(RESULTS, exist_ok=True)
    path = os.path.join(RESULTS, "partitioned_inference.json")
    with open(path, "w") as fh:
        json.dump(res, fh, indent=1)
    check_acceptance(res)
    print(f"wrote {path} — acceptance OK (bitwise fp32 parity at every "
          f"device count, >= {SPEEDUP_FLOOR}x measured speedup over the "
          f"padded oracle at {GATE_DEVICES} devices)")
