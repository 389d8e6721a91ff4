#!/usr/bin/env python3
"""Smoke run of the packed GNN serving path on a TPU, at the paper widths.

  python3 chip_smoke.py               # one chip: serving + parity phases
  python3 chip_smoke.py --four-chips  # four chips: sharded + partitioned

One process drives the chip. With no option it runs two phases:

* serving: ``launch/serve.gnn_main`` serves 256 qm9-like requests in
  packed batches of 32 graphs with the ``gcn`` model at
  ``configs.gnn.benchmark_config`` widths (fp32), through the wave drain
  and the continuous scheduler, once per aggregation backend. Every
  request must be served: none failed, rejected or answered by the
  padded fallback. The Pallas program must hold a Mosaic kernel
  (``tpu_custom_call``).
* parity: every registered conv runs one packed batch on both backends
  at fp32 and bf16; each output is compared with the same model's fp32
  XLA program run on the host CPU device of this process.

``--four-chips`` runs only the multi-chip paths users reach through
``serve.py --shards N`` and the oversize route: the sharded drain over a
4-device ``("data",)`` mesh against the single-device packed program,
and one oversize graph through ``apply_packed_partitioned`` against the
padded oracle ``G.apply``. It checks that the sharded inputs and outputs
span four distinct devices.

Every printed time is a smoke reading, not a benchmark metric. The last
line of standard output is one JSON object, ``{"ok": true, "device":
{...}}``, printed only when every phase passed. Without a TPU the script
exits non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
REQUESTS = 256
BATCH_GRAPHS = 32
# max |device - reference| / max |reference| over the valid output rows.
# fp32: both sides run at "highest" matmul precision, so only summation
# order differs. bf16: the policy rounds activations and weights to bf16
# (8-bit mantissa) at every layer, against an fp32 reference.
TOL = {"fp32": 1e-4, "bf16": 5e-2}


def log(msg: str):
    print(f"smoke: {msg}", flush=True)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(count: int) -> dict:
    info = device_info()
    if info["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{info['platform']!r}); nothing was run")
    if info["count"] < count:
        raise SystemExit(f"chip_smoke: need {count} TPU chips, JAX found "
                         f"{info['count']}")
    return info


def qm9_queue(n: int, batch_graphs: int):
    """``n`` qm9-like requests and the packed budgets serve.py derives
    for ``batch_graphs`` graphs per batch."""
    from repro.configs.gnn import DATASETS
    from repro.data import pipeline as P
    ds = DATASETS["qm9"]
    nb = P.size_budget(batch_graphs, ds.avg_nodes)
    eb = P.size_budget(batch_graphs, ds.avg_nodes * ds.avg_degree)
    return [P.make_graph(ds, i) for i in range(n)], nb, eb


def rel_err(got, ref) -> float:
    import numpy as np
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))


def serve_args(*extra):
    from repro.launch import serve
    return serve.parse_args(["--gnn", "--conv", "gcn", *extra])


# ------------------------------------------------------------ serving --
def serving_phase(requests: int = REQUESTS,
                  batch_graphs: int = BATCH_GRAPHS) -> None:
    from repro.launch import serve
    for backend in ("xla", "pallas"):
        for scheduler in ("wave", "continuous"):
            args = serve_args("--requests", str(requests),
                              "--batch-graphs", str(batch_graphs),
                              "--agg-backend", backend,
                              "--scheduler", scheduler)
            t0 = time.perf_counter()
            stats = serve.gnn_main(args)
            wall = time.perf_counter() - t0
            rejected = sum(v for k, v in stats.items()
                           if k.startswith("rejected_"))
            bad = {"served": stats["served"] != requests,
                   "failed": stats.get("failed", 0) != 0,
                   "rejected": rejected != 0,
                   "fallback": stats["fallback_served"] != 0
                   or stats["partitioned_served"] != 0}
            log(f"serve backend={backend} scheduler={scheduler} "
                f"served={stats['served']}/{requests} "
                f"failed={stats.get('failed', 0)} rejected={rejected} "
                f"fallback={stats['fallback_served']} "
                f"wall_s={wall:.3f} first_error={stats.get('first_error')}")
            failed = [k for k, v in bad.items() if v]
            if failed:
                raise SystemExit(f"chip_smoke: serving {backend}/"
                                 f"{scheduler} failed checks {failed}")


def pallas_program_text(batch_graphs: int = BATCH_GRAPHS) -> str:
    """Compiled text of the packed program ``serve --agg-backend pallas``
    runs: same config, policy and batch shapes."""
    import jax

    from repro.core import aggregations as A
    from repro.core import gnn_model as G
    from repro.data import pipeline as P
    from repro.launch import serve
    from repro.nn import param as prm
    args = serve_args("--agg-backend", "pallas")
    cfg = serve.gnn_model_config(args)
    queue, nb, eb = qm9_queue(batch_graphs, batch_graphs)
    batch, _ = P.pack_graphs(queue, nb, eb, batch_graphs)
    params = prm.abstract(G.model_plan(cfg))
    policy = G.resolve_policy(cfg)
    with A.backend_scope("pallas"):
        return jax.jit(lambda p, b: G.apply_packed(p, cfg, b, None, policy)) \
            .lower(params, G.packed_to_device(batch)).compile().as_text()


# ------------------------------------------------------------- parity --
def parity_phase(convs=None, batch_graphs: int = BATCH_GRAPHS) -> None:
    import jax
    import numpy as np

    from repro.core import aggregations as A
    from repro.core import convs as Cv
    from repro.core import gnn_model as G
    from repro.core import quantization as Q
    from repro.data import pipeline as P
    from repro.launch import serve
    from repro.nn import param as prm
    cpu = jax.devices("cpu")[0]
    dev = jax.devices()[0]
    queue, nb, eb = qm9_queue(batch_graphs, batch_graphs)
    host_batch, k = P.pack_graphs(queue, nb, eb, batch_graphs)
    host_batch = {key: v for key, v in host_batch.items() if key != "y"}
    batch = jax.device_put(host_batch, dev)
    worst = {}
    for conv in convs or Cv.CONV_TYPES:
        cfg = serve.gnn_model_config(serve_args("--conv", conv))
        params = prm.materialize(G.model_plan(cfg),
                                 jax.random.key(SEED))
        fp32 = Q.resolve_policy("fp32", cfg.gnn_num_layers)
        with jax.default_matmul_precision("highest"), \
                A.backend_scope("xla"):
            ref = jax.jit(lambda p, b: G.apply_packed(p, cfg, b, None, fp32))(
                jax.device_put(params, cpu), jax.device_put(host_batch, cpu))
        ref = np.asarray(ref)[:k]
        for backend in ("xla", "pallas"):
            for prec in ("fp32", "bf16"):
                pol = Q.resolve_policy(prec, cfg.gnn_num_layers)
                t0 = time.perf_counter()
                with jax.default_matmul_precision("highest"), \
                        A.backend_scope(backend):
                    compiled = jax.jit(
                        lambda p, b: G.apply_packed(p, cfg, b, None, pol)) \
                        .lower(params, batch).compile()
                compile_s = time.perf_counter() - t0
                out = compiled(params, batch)
                platform = next(iter(out.devices())).platform
                err = rel_err(np.asarray(out)[:k], ref)
                ok = err <= TOL[prec] and platform == dev.platform
                worst[prec] = max(worst.get(prec, 0.0), err)
                log(f"parity conv={conv} backend={backend} precision={prec} "
                    f"on={platform} compile_s={compile_s:.3f} "
                    f"max_rel_err={err:.3e} tol={TOL[prec]:.0e} "
                    f"{'ok' if ok else 'FAIL'}")
                if not ok:
                    raise SystemExit(f"chip_smoke: parity {conv}/{backend}/"
                                     f"{prec} error {err:.3e} > "
                                     f"{TOL[prec]:.0e} (or ran on "
                                     f"{platform})")
    log(f"parity worst max_rel_err {worst}")


# --------------------------------------------------------- four chips --
def four_chip_phase(requests: int = REQUESTS,
                    batch_graphs: int = BATCH_GRAPHS) -> None:
    import jax
    import numpy as np

    from repro.core import gnn_model as G
    from repro.core import quantization as Q
    from repro.data import pipeline as P
    from repro.distributed.sharding import graph_batch_sharding
    from repro.launch import serve
    from repro.launch.mesh import make_data_mesh
    from repro.nn import param as prm
    n_dev = 4
    mesh = make_data_mesh(n_dev)
    cfg = serve.gnn_model_config(serve_args())
    params = prm.materialize(G.model_plan(cfg), jax.random.key(SEED))
    pol = Q.resolve_policy("fp32", cfg.gnn_num_layers)
    queue, nb, eb = qm9_queue(requests, batch_graphs)

    with jax.default_matmul_precision("highest"):
        single = jax.jit(lambda p, b: G.apply_packed(p, cfg, b, None, pol))
        sharded = G.make_sharded_apply(cfg, mesh, None, pol)

        # the drain users reach through serve.py --shards 4
        t0 = time.perf_counter()
        _, stats = serve.drain_gnn_queue_sharded(
            sharded, params, queue, nb, eb, batch_graphs, n_dev,
            task=cfg.task)
        log(f"sharded drain served={stats['served']}/{requests} "
            f"waves={stats['n_batches']} "
            f"wall_s={time.perf_counter() - t0:.3f}")
        if stats["served"] != requests:
            raise SystemExit("chip_smoke: sharded drain lost requests")

        # each wave against the single-device packed program, shard by
        # shard, with the placement checked on the way
        waves, _ = P.pack_dataset(queue, nb, eb, batch_graphs,
                                  num_shards=n_dev)
        worst = 0.0
        for w in waves:
            stacked = jax.device_put(G.stack_shards(w),
                                     graph_batch_sharding(mesh))
            in_devs = {d for x in jax.tree_util.tree_leaves(stacked)
                       for d in x.devices()}
            out = sharded(params, stacked)
            out_devs = {s.device for s in out.addressable_shards}
            if len(in_devs) != n_dev or len(out_devs) != n_dev:
                raise SystemExit(f"chip_smoke: sharded inputs on "
                                 f"{len(in_devs)} devices, outputs on "
                                 f"{len(out_devs)}; expected {n_dev}")
            for i, b in enumerate(w.shards):
                kk = int(b["num_graphs"])
                ref = single(params, G.packed_to_device(b))
                worst = max(worst, rel_err(np.asarray(out[i])[:kk],
                                           np.asarray(ref)[:kk]))
        ok = worst <= TOL["fp32"]
        log(f"sharded parity waves={len(waves)} inputs_on={len(in_devs)} "
            f"outputs_on={len(out_devs)} devices "
            f"max_rel_err={worst:.3e} tol={TOL['fp32']:.0e} "
            f"{'ok' if ok else 'FAIL'}")
        if not ok:
            raise SystemExit("chip_smoke: sharded parity failed")

        # one oversize graph split over the mesh against the padded oracle
        from repro.configs.gnn import DATASETS
        ds = DATASETS["qm9"]
        big = dataclasses.replace(
            ds, avg_nodes=int(1.2 * nb), max_nodes=max(ds.max_nodes, 4 * nb),
            max_edges=max(ds.max_edges, 4 * eb), seed=ds.seed + 0x0B1)
        g = P.make_graph(big, 0)
        part = P.partition_graph(g, n_dev, nb, eb)
        stacked = jax.device_put(G.stack_shards(part.parts),
                                 graph_batch_sharding(mesh))
        in_devs = {d for x in jax.tree_util.tree_leaves(stacked)
                   for d in x.devices()}
        t0 = time.perf_counter()
        got = G.make_partitioned_apply(cfg, mesh, None, pol,
                                       out_rows=part.padded_nodes)(
            params, stacked)
        got = np.asarray(got)
        wall = time.perf_counter() - t0
        oracle = jax.jit(lambda p, el: G.apply(p, cfg, el, None, pol))
        ref = np.asarray(oracle(params, serve._fallback_input(g)))
        err = rel_err(got, ref)
        ok = err <= TOL["fp32"] and len(in_devs) == n_dev
        log(f"partitioned graph nodes={g.num_nodes} edges={g.num_edges} "
            f"inputs_on={len(in_devs)} devices wall_s={wall:.3f} "
            f"max_rel_err={err:.3e} tol={TOL['fp32']:.0e} "
            f"{'ok' if ok else 'FAIL'}")
        if not ok:
            raise SystemExit("chip_smoke: partitioned parity failed")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded and partitioned checks "
                         "on four chips")
    args = ap.parse_args(argv)
    info = require_tpu(4 if args.four_chips else 1)

    from repro.launch import compile_cache
    cache = compile_cache.enable()
    log(f"device {info} compile cache {cache}")
    log("times below are smoke readings, not benchmark metrics")
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase()
        log(f"four-chip phase wall_s={time.perf_counter() - t0:.3f}")
    else:
        serving_phase()
        text = pallas_program_text()
        if "tpu_custom_call" not in text:
            raise SystemExit("chip_smoke: the Pallas serving program holds "
                             "no tpu_custom_call")
        log(f"pallas program holds {text.count('tpu_custom_call')} "
            f"tpu_custom_call sites")
        log(f"serving phase wall_s={time.perf_counter() - t0:.3f}")
        t1 = time.perf_counter()
        parity_phase()
        log(f"parity phase wall_s={time.perf_counter() - t1:.3f}")
    print(json.dumps({"ok": True, "device": device_info()}), flush=True)


if __name__ == "__main__":
    main()
