"""Open-loop serving latency: continuous batching vs the wave drain.

The wave drain (``launch.serve.drain_gnn_queue``) reports offline batch
throughput; this benchmark measures what the ROADMAP's "millions of
users" goal actually needs — **p50/p99 request latency and sustained
graphs/s under a live arrival process**. A seeded open-loop Poisson
trace is served twice through identical executors:

* **continuous** — ``runtime.scheduler.ContinuousScheduler``: requests
  feed continuously into a partially-filled packed batch; launch on
  deadline expiry or budget-full,
* **wave** — ``runtime.scheduler.simulate_wave_drain``: the oracle of
  today's synchronous drain (collect a ``batch_graphs`` window, pack,
  run, repeat) on the same virtual timeline.

Determinism: the clock is virtual and each launch's service time is the
*modeled* packed-program latency from ``Project.run_synthesis`` (a
fixed-shape program costs the same however full the batch is, so the
constant-per-launch model is honest) — identical numbers on every run,
no sleeps. The **outputs** are the real jitted packed program's, so the
run doubles as an exactly-once parity check: every request's answer
must match the offline single-graph packed reference (PARITY_TOL), for
both schedulers.

Acceptance (``check_acceptance``, the CI ``--smoke`` gate):

* parity: every served request matches the offline reference,
* exactly-once: every request is answered exactly once,
* continuous p99 < wave p99 at every offered load,
* continuous sustained graphs/s >= THROUGHPUT_FLOOR x wave.

  PYTHONPATH=src python benchmarks/serving_latency.py [--smoke]
      [--loads 128 256 512] [--n 384] [--batch-graphs 16]
      [--deadline-ms 20]

JSON lands in benchmarks/results/serving_latency.json.
"""
from __future__ import annotations

import argparse
import json
import os

import numpy as np

RESULTS = os.path.join(os.path.dirname(__file__), "results")
PARITY_TOL = 1e-5        # scheduler outputs vs offline packed reference
THROUGHPUT_FLOOR = 0.95  # continuous graphs/s >= floor x wave graphs/s

#: full-sweep tenant mixture exercising the SLO tiers (smoke uses a
#: single default tenant so the closed gates stay trivially comparable)
TENANT_MIX = (("premium", 0.2), ("standard", 0.5), ("batch", 0.3))


def build(batch_graphs: int):
    """Model + budgets + jitted programs + modeled per-launch service."""
    import jax

    from repro.configs.gnn import DATASETS
    from repro.core import gnn_model as G
    from repro.core.project import Project
    from repro.data import pipeline as P
    from repro.nn import param as prm

    ds = DATASETS["qm9"]
    cfg = G.GNNModelConfig(
        graph_input_feature_dim=ds.node_feat_dim,
        graph_input_edge_dim=ds.edge_feat_dim,
        gnn_hidden_dim=64, gnn_num_layers=2, gnn_output_dim=32,
        gnn_conv="gcn", gnn_skip_connection=True,
        avg_degree=float(ds.avg_degree),
        mlp_head=G.MLPConfig(in_dim=32 * 3, out_dim=1, hidden_dim=32,
                             hidden_layers=2))
    params = prm.materialize(G.model_plan(cfg), jax.random.key(0))
    node_budget = P.size_budget(batch_graphs, ds.avg_nodes)
    edge_budget = P.size_budget(batch_graphs,
                                ds.avg_nodes * ds.avg_degree)
    fn = jax.jit(lambda p, b: G.apply_packed(p, cfg, b))
    fallback = jax.jit(lambda p, el: G.apply(p, cfg, el))

    proj = Project("serving_latency", cfg, "bench",
                   "/tmp/gnnb_serving_latency",
                   max_nodes=ds.max_nodes, max_edges=ds.max_edges,
                   num_nodes_guess=ds.avg_nodes,
                   num_edges_guess=ds.avg_nodes * ds.avg_degree,
                   degree_guess=ds.avg_degree, batch_graphs=batch_graphs)
    proj.gen_hw_model()
    service_s = float(proj.run_synthesis()["packed"]["latency_s"])

    def batch_fn(batch):
        return np.asarray(jax.block_until_ready(
            fn(params, G.packed_to_device(batch))))

    def fallback_fn(g):
        el = {"node_feat": np.asarray(g.node_feat),
              "edge_index": np.asarray(g.edge_index),
              "edge_feat": np.asarray(g.edge_feat),
              "num_nodes": np.int32(g.num_nodes)}
        return np.asarray(jax.block_until_ready(fallback(params, el)))

    return {"ds": ds, "batch_fn": batch_fn, "fallback_fn": fallback_fn,
            "node_budget": node_budget, "edge_budget": edge_budget,
            "batch_graphs": batch_graphs, "service_s": service_s}


def offline_reference(env, trace):
    """Per-request oracle: each graph packed alone through the same
    jitted program (same static shapes -> same compiled program)."""
    from repro.data import pipeline as P
    refs = {}
    for i, (_, g, _) in enumerate(trace):
        batch, _ = P.pack_graphs([g], env["node_budget"],
                                 env["edge_budget"], env["batch_graphs"])
        refs[i] = env["batch_fn"](batch)[0]
    return refs


def _parity(responses, refs) -> float:
    err = 0.0
    for r in responses:
        if r.status == "served_packed" and r.output is not None:
            err = max(err, float(np.abs(r.output - refs[r.req_id]).max()))
    return err


def run_point(env, load: float, n: int, deadline_s: float, seed: int,
              tenants=(("default", 1.0),)) -> dict:
    from repro.runtime import scheduler as S
    cfg = S.SchedulerConfig(
        node_budget=env["node_budget"], edge_budget=env["edge_budget"],
        max_graphs=env["batch_graphs"], max_queue_depth=4 * n,
        tiers=S.DEFAULT_TIERS,
        default_tier=S.SLOTier("standard", deadline_s, 1))
    trace = S.poisson_trace(n, load, env["ds"], seed=seed, tenants=tenants)
    refs = offline_reference(env, trace)

    def executor():
        return S.SimExecutor(S.constant_service(env["service_s"]),
                             batch_fn=env["batch_fn"],
                             fallback_fn=env["fallback_fn"])

    cont = S.ContinuousScheduler(cfg, executor())
    S.run_trace(cont, trace)
    cs = cont.summary()
    wave_resp, ws = S.simulate_wave_drain(trace, cfg, executor())

    def ids(resps):
        return sorted(r.req_id for r in resps)

    assert ids(cont.responses) == list(range(n)), "continuous exactly-once"
    assert ids(wave_resp) == list(range(n)), "wave exactly-once"
    return {
        "load_graphs_per_s": load,
        "n_requests": n,
        "deadline_s": deadline_s,
        "parity_max_err": max(_parity(cont.responses, refs),
                              _parity(wave_resp, refs)),
        "continuous": {k: cs[k] for k in (
            "served", "fallback_served", "rejected_queue_full", "failed",
            "n_launches", "mean_batch_fill", "p50_latency_s",
            "p99_latency_s", "graphs_per_s", "per_tenant")},
        "wave": {k: ws[k] for k in (
            "served", "fallback_served", "n_launches", "mean_batch_fill",
            "p50_latency_s", "p99_latency_s", "graphs_per_s")},
    }


def sweep(loads, n: int, batch_graphs: int, deadline_ms: float,
          seed: int = 0, tenant_mix: bool = False, log=print) -> dict:
    env = build(batch_graphs)
    points = []
    for load in loads:
        pt = run_point(env, float(load), n, deadline_ms / 1e3, seed,
                       tenants=TENANT_MIX if tenant_mix
                       else (("default", 1.0),))
        points.append(pt)
        if log:
            c, w = pt["continuous"], pt["wave"]

            def ms(v):      # percentiles are None when nothing served
                return "    n/a" if v is None else f"{v * 1e3:7.2f}"
            log(f"load={load:6.0f} graphs/s | continuous p50 "
                f"{ms(c['p50_latency_s'])} ms  p99 "
                f"{ms(c['p99_latency_s'])} ms  "
                f"({c['graphs_per_s']:7.0f} graphs/s, fill "
                f"{c['mean_batch_fill'] * 100:3.0f}%) | wave p50 "
                f"{ms(w['p50_latency_s'])} ms  p99 "
                f"{ms(w['p99_latency_s'])} ms  "
                f"({w['graphs_per_s']:7.0f} graphs/s) | parity "
                f"{pt['parity_max_err']:.1e}")
    return {"dataset": "qm9", "conv": "gcn", "n_requests": n,
            "batch_graphs": batch_graphs, "deadline_ms": deadline_ms,
            "service_s": env["service_s"], "parity_tol": PARITY_TOL,
            "throughput_floor": THROUGHPUT_FLOOR, "points": points}


def check_acceptance(res: dict):
    """Parity at every load; continuous must beat the wave drain on p99
    and hold >= THROUGHPUT_FLOOR of its sustained graphs/s. Percentiles
    are explicit nulls when nothing was served, so the latency gates
    only apply after the served>0 gate passes."""
    for pt in res["points"]:
        load = pt["load_graphs_per_s"]
        assert pt["parity_max_err"] < res["parity_tol"], \
            (load, pt["parity_max_err"])
        c, w = pt["continuous"], pt["wave"]
        assert c["served"] > 0 and w["served"] > 0, \
            (load, c["served"], w["served"])
        assert c["p99_latency_s"] is not None \
            and w["p99_latency_s"] is not None, load
        assert c["p99_latency_s"] < w["p99_latency_s"], \
            (load, c["p99_latency_s"], w["p99_latency_s"])
        assert c["graphs_per_s"] >= res["throughput_floor"] \
            * w["graphs_per_s"], \
            (load, c["graphs_per_s"], w["graphs_per_s"])


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="single-load run + parity/p99/throughput gates "
                         "(the CI step)")
    ap.add_argument("--loads", type=float, nargs="+",
                    default=[128, 256, 512])
    ap.add_argument("--n", type=int, default=384)
    ap.add_argument("--batch-graphs", type=int, default=16)
    ap.add_argument("--deadline-ms", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.smoke:
        res = sweep([256], 160, 16, args.deadline_ms, args.seed)
    else:
        res = sweep(args.loads, args.n, args.batch_graphs,
                    args.deadline_ms, args.seed, tenant_mix=True)
    os.makedirs(RESULTS, exist_ok=True)
    path = os.path.join(RESULTS, "serving_latency.json")
    with open(path, "w") as fh:
        json.dump(res, fh, indent=1)
    check_acceptance(res)
    print(f"wrote {path} — acceptance OK (parity < {PARITY_TOL}, "
          f"continuous p99 < wave p99 and graphs/s >= "
          f"{THROUGHPUT_FLOOR}x wave at every offered load)")
