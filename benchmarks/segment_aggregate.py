"""Segment-aggregation backend benchmark on packed QM9-like batches.

Times the fused Pallas edge-block kernel (interpret mode everywhere, and
compiled where a TPU backend is available) against the XLA
jax.ops.segment_* path, for every paper aggregation, over the edge stream
of a real packed GraphBatch — the exact layout the convs lower through.
Also sweeps the DSE tile knobs (edge_block/node_block) so measured
timings can seed the perf-model database.

  PYTHONPATH=src python benchmarks/segment_aggregate.py \
      [--batch-graphs 32] [--feat-dim 64] [--repeats 5]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.gnn import DATASETS
from repro.core.aggregations import AGGREGATIONS, segment_aggregate
from repro.data import pipeline as P

RESULTS = os.path.join(os.path.dirname(__file__), "results")


def _time(fn, *args, repeats: int = 5) -> float:
    jax.block_until_ready(fn(*args))                  # compile / warm up
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def run(batch_graphs: int = 32, feat_dim: int = 64, repeats: int = 5,
        tiles=((64, 32), (128, 64), (256, 128)), log=print) -> dict:
    ds = DATASETS["qm9"]
    node_budget = P.size_budget(batch_graphs, ds.avg_nodes)
    edge_budget = P.size_budget(batch_graphs, ds.avg_nodes * ds.avg_degree)
    graphs = [P.make_graph(ds, i) for i in range(batch_graphs)]
    batch, k = P.pack_graphs(graphs, node_budget, edge_budget, batch_graphs)

    rng = np.random.default_rng(0)
    msgs = jnp.asarray(rng.standard_normal((edge_budget, feat_dim)),
                       jnp.float32)
    dst = jnp.asarray(batch["edge_index"][:, 1])
    valid = jnp.asarray(batch["edge_index"][:, 0] >= 0)
    n = node_budget

    on_tpu = jax.default_backend() == "tpu"
    res = {
        "dataset": "qm9", "batch_graphs": batch_graphs,
        "graphs_packed": int(k), "node_budget": node_budget,
        "edge_budget": edge_budget, "feat_dim": feat_dim,
        "jax_backend": jax.default_backend(), "aggregations": {},
    }
    for agg in AGGREGATIONS:
        xla = jax.jit(lambda m, s, v: segment_aggregate(
            agg, m, s, n, v, backend="xla"))
        xla_s = _time(xla, msgs, dst, valid, repeats=repeats)
        want = np.asarray(xla(msgs, dst, valid))
        entry = {"xla_s": xla_s, "tiles": {}}
        for eb, nb in tiles:
            def pallas_fn(m, s, v, eb=eb, nb=nb, interpret=True):
                return segment_aggregate(agg, m, s, n, v,
                                         backend="pallas", edge_block=eb,
                                         node_block=nb,
                                         interpret=interpret)
            pal = jax.jit(pallas_fn)
            pal_s = _time(pal, msgs, dst, valid, repeats=repeats)
            diff = float(np.max(np.abs(np.asarray(
                pal(msgs, dst, valid)) - want)))
            tile = {"pallas_interpret_s": pal_s, "max_abs_diff": diff,
                    "interpret_speedup_vs_xla": xla_s / pal_s}
            if on_tpu:   # compiled Pallas only where Mosaic is available
                comp = jax.jit(lambda m, s, v: pallas_fn(
                    m, s, v, interpret=False))
                tile["pallas_compiled_s"] = _time(comp, msgs, dst, valid,
                                                  repeats=repeats)
                tile["compiled_speedup_vs_xla"] = \
                    xla_s / tile["pallas_compiled_s"]
            entry["tiles"][f"eb{eb}_nb{nb}"] = tile
            assert diff < 1e-5, (agg, eb, nb, diff)
        res["aggregations"][agg] = entry
        if log:
            best_tile = min(entry["tiles"].items(),
                            key=lambda kv: kv[1]["pallas_interpret_s"])
            log(f"{agg:>4}: xla {xla_s * 1e3:7.3f} ms | pallas "
                f"{best_tile[1]['pallas_interpret_s'] * 1e3:7.3f} ms "
                f"(interpret, best tile {best_tile[0]}, max diff "
                f"{best_tile[1]['max_abs_diff']:.1e})")
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "segment_aggregate.json"), "w") as f:
        json.dump(res, f, indent=1)
    return res


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-graphs", type=int, default=32)
    ap.add_argument("--feat-dim", type=int, default=64)
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args()
    res = run(args.batch_graphs, args.feat_dim, args.repeats)
    print(f"wrote {os.path.join(RESULTS, 'segment_aggregate.json')} "
          f"({res['jax_backend']} backend, equivalence < 1e-5 on all "
          f"aggregations and tiles)")
