"""Batched serving drivers.

LM mode (default): prefill + decode loop with donated KV caches.

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --reduced \
      --batch 4 --prompt-len 32 --gen 32

GNN mode (--gnn): drains a graph request queue through fixed-shape packed
GraphBatch programs — one jitted program, budget-sized buffers, reported
in graphs/s (DESIGN_BATCHING.md). Admission mirrors the continuous
scheduler's statuses: malformed graphs are rejected explicitly
(``rejected_invalid``, data.pipeline.validate_graph), and requests too
large for the packed budgets split across the local device pool through
the intra-graph partitioned SPMD program when >= 2 devices exist
(``partitioned_served``; halo exchange between layers, docs/SERVING.md)
— the padded per-graph oracle stays as the no-mesh fallback
(``fallback_served``), and with neither program oversize requests get
per-request ``rejected_oversize`` outcomes, never a silent drop. ``--precision``
serves through
a low-precision PrecisionPolicy datapath (bf16 / int8 tiles, fp32
accumulation; int8 grids are max-abs calibrated on the warmup batch) and
reports the output error vs the fp32 program next to the throughput.

``--shards N`` drains the queue into per-device packed shard waves over
a ("data",) device mesh instead — one SPMD program, params replicated,
each device consuming its own shard (the oversize fallback is
unchanged).

``--scheduler continuous`` swaps the synchronous wave drain for the
continuous-batching scheduler (runtime.scheduler): the queue is
replayed as an open-loop Poisson arrival process at ``--load`` graphs/s
on a virtual clock, requests feed continuously into partially-filled
packed batches, and a batch launches on ``--deadline-ms`` expiry or
budget-full; measured service times make the reported p50/p99
traffic-shaped while the compute is real. Full lifecycle:
docs/SERVING.md.

``--gnn`` serves ``configs.gnn.benchmark_config`` (the paper's widths)
unless ``--reduced`` asks for the toy config. A request that ends
``failed`` makes the process exit non-zero with the first executor
error; serving injects no faults, so every failure is real.

  PYTHONPATH=src python -m repro.launch.serve --gnn --conv gcn \
      --requests 256 --batch-graphs 32 [--agg-backend pallas] \
      [--dataflow auto|aggregate_first|transform_first] \
      [--precision fp32|bf16|int8] [--shards 4] \
      [--scheduler continuous --load 512 --deadline-ms 50]
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import ARCHS, get_config
from repro.core import convs as Cv
from repro.launch import compile_cache
from repro.models import lm
from repro.nn import param as prm


def pad_caches(prefill_caches, full_caches):
    """Write prompt-length caches into the full-length serving buffers."""
    def place(full, part):
        if full.shape == part.shape:
            return part.astype(full.dtype)
        return jax.lax.dynamic_update_slice(
            full, part.astype(full.dtype), (0,) * full.ndim)
    return jax.tree_util.tree_map(place, full_caches, prefill_caches)


def _fallback_input(g) -> dict:
    """Padded per-graph oracle input for one oversize Graph request."""
    return {"node_feat": jnp.asarray(g.node_feat),
            "edge_index": jnp.asarray(g.edge_index),
            "edge_feat": jnp.asarray(g.edge_feat),
            "num_nodes": jnp.int32(g.num_nodes)}


def _admit(queue, node_budget: int, edge_budget: int, *,
           can_fallback: bool, can_partition: bool = False,
           validate: bool = True):
    """Admission screen of the wave drains, mirroring the continuous
    scheduler's ``submit``: every request is routed to exactly one
    outcome up front — packable, oversize (answered by the partitioned
    SPMD program when ``can_partition``, else the padded fallback), or
    an explicit per-request rejection (``rejected_oversize`` when
    neither oversize program exists, ``rejected_invalid`` when
    ``validate_graph`` says the graph is malformed) — never a silent
    drop. The classification is *mesh-aware*: ``can_partition`` is the
    same predicate the continuous scheduler's executors advertise, so
    the wave drains and the scheduler agree on which program answers an
    oversize request. Returns (packable, oversize, outcomes);
    ``outcomes[i]`` carries the queue index, the status
    (continuous-scheduler status names — oversize statuses are the
    *planned* route, reconciled to the actual one after launch), and a
    reason for rejections."""
    from repro.data import pipeline as P
    from repro.runtime import scheduler as S
    packable, oversize, outcomes = [], [], []
    for i, g in enumerate(queue):
        if validate:
            reason = P.validate_graph(g)
            if reason is not None:
                outcomes.append({"index": i, "status": S.REJECTED_INVALID,
                                 "reason": reason})
                continue
        if P.graph_fits_budget(g, node_budget, edge_budget):
            packable.append(g)
            outcomes.append({"index": i, "status": S.SERVED_PACKED})
        elif can_partition or can_fallback:
            oversize.append(g)
            outcomes.append({"index": i, "status":
                             S.SERVED_PARTITIONED if can_partition
                             else S.SERVED_FALLBACK})
        else:
            outcomes.append({
                "index": i, "status": S.REJECTED_OVERSIZE,
                "reason": f"{g.num_nodes} nodes/{g.num_edges} edges exceed "
                          f"the packed budgets ({node_budget} nodes/"
                          f"{edge_budget} edges) and no partitioned or "
                          "fallback program is available"})
    return packable, oversize, outcomes


def _reconcile_oversize(outcomes, over_status):
    """Rewrite the oversize outcomes' *planned* route with the actual
    post-launch one (partition infeasibility reroutes a graph to the
    padded fallback, or to an explicit rejection when none exists), so
    ``outcomes`` and the partitioned/fallback counts always agree."""
    from repro.runtime import scheduler as S
    it = iter(over_status)
    for o in outcomes:
        if o["status"] in (S.SERVED_PARTITIONED, S.SERVED_FALLBACK):
            o["status"] = next(it)
    return outcomes


def _rejection_stats(stats: dict, outcomes) -> dict:
    """Fold per-request admission outcomes into a wave drain's stats.
    ``dropped`` stays as a legacy alias of ``rejected_oversize``."""
    from repro.runtime import scheduler as S
    stats["outcomes"] = outcomes
    stats["rejected_oversize"] = sum(
        1 for o in outcomes if o["status"] == S.REJECTED_OVERSIZE)
    stats["rejected_invalid"] = sum(
        1 for o in outcomes if o["status"] == S.REJECTED_INVALID)
    stats["dropped"] = stats["rejected_oversize"]
    return stats


def _launch_packed(run_batch, batches, oversize, fallback_fn, *,
                   graphs_in, slots_in, slot_capacity: int,
                   partition_fn=None):
    """Shared pack-and-launch body of the wave drains (and of anything
    else that runs a prepacked batch list): run every batch through
    ``run_batch``, answer oversize requests through ``partition_fn``
    (the intra-graph partitioned SPMD program; returns None when the
    graph cannot split under the per-device budgets) and ``fallback_fn``
    (the padded per-graph oracle on a ``_fallback_input`` dict), block,
    and account. Each oversize graph resolves to exactly one of
    partitioned / fallback / rejected-oversize — never double-counted.
    ``graphs_in``/``slots_in`` count the graphs and occupied node slots
    of one batch (they differ between the single-device and sharded
    layouts). Returns (batch_outs, oversize_outs, oversize_statuses,
    stats); ``oversize_outs``/``oversize_statuses`` line up with
    ``oversize`` (rejected graphs carry a None output)."""
    from repro.runtime import scheduler as S
    outs = []
    served = 0
    slots_used = 0
    t0 = time.perf_counter()
    for b in batches:
        outs.append(run_batch(b))
        served += graphs_in(b)
        slots_used += slots_in(b)
    over_outs, over_status = [], []
    for g in oversize:
        out = None if partition_fn is None else partition_fn(g)
        if out is not None:
            over_outs.append(out)
            over_status.append(S.SERVED_PARTITIONED)
        elif fallback_fn is not None:
            over_outs.append(fallback_fn(_fallback_input(g)))
            over_status.append(S.SERVED_FALLBACK)
        else:
            over_outs.append(None)
            over_status.append(S.REJECTED_OVERSIZE)
    live = [o for o in over_outs if o is not None]
    jax.block_until_ready(outs + live)
    total_s = time.perf_counter() - t0
    n_part = over_status.count(S.SERVED_PARTITIONED)
    n_fallback = over_status.count(S.SERVED_FALLBACK)
    stats = {
        "served": served + n_part + n_fallback,
        "packed_served": served,
        "partitioned_served": n_part,
        "fallback_served": n_fallback,
        "n_batches": len(batches),
        "graphs_per_s": (served + n_part + n_fallback)
        / max(total_s, 1e-12),
        "node_slot_utilization": slots_used / max(slot_capacity, 1),
        "total_s": total_s,
    }
    return outs, over_outs, over_status, stats


def drain_gnn_queue(fn, params, queue, node_budget: int, edge_budget: int,
                    batch_graphs: int, fallback_fn=None, *,
                    partition_fn=None, validate: bool = True):
    """Synchronous wave drain of ``queue`` (a list of data.pipeline.Graph
    requests) through the packed program ``fn``; every call sees the same
    static shapes, so XLA compiles exactly once. Returns
    (outputs per batch, stats).

    Request lifecycle (docs/SERVING.md): requests that fit the budgets
    are greedily packed into fixed-shape GraphBatches and answered by
    the packed program. Requests too large for the budgets cannot ride
    a GraphBatch; with ``partition_fn`` (the intra-graph partitioned
    SPMD program, ``G.apply_packed_partitioned`` behind a
    graph -> output-or-None callable) each one splits across the device
    mesh and ``stats["partitioned_served"]`` counts them; with
    ``fallback_fn`` (the padded per-graph oracle ``G.apply``, jitted)
    graphs the partitioner cannot split — or every oversize graph when
    no mesh exists — are answered individually through it
    (``stats["fallback_served"]``). Without either program each
    oversize request gets an explicit per-request ``rejected_oversize``
    outcome, and malformed graphs get ``rejected_invalid``
    (``validate=False`` skips the screen) — ``stats["outcomes"]`` lists
    every request's status under the same names the continuous
    scheduler uses, and ``stats["dropped"]`` stays as a legacy alias of
    ``rejected_oversize``.

    This drain is the offline-throughput baseline (and parity oracle)
    for the continuous-batching scheduler — see
    ``drain_gnn_queue_continuous`` for the latency-aware path."""
    from repro.core import gnn_model as G
    from repro.data import pipeline as P
    packable, oversize, outcomes = _admit(
        queue, node_budget, edge_budget,
        can_fallback=fallback_fn is not None,
        can_partition=partition_fn is not None, validate=validate)
    batches, leftover = P.pack_dataset(packable, node_budget, edge_budget,
                                       batch_graphs)
    assert not leftover, "_admit already screened for budget fit"
    outs, over_outs, over_status, stats = _launch_packed(
        lambda b: fn(params, G.packed_to_device(b)), batches, oversize,
        None if fallback_fn is None else (lambda el: fallback_fn(params, el)),
        partition_fn=partition_fn,
        graphs_in=lambda b: int(b["num_graphs"]),
        slots_in=lambda b: int((b["node_graph_id"] < batch_graphs).sum()),
        slot_capacity=len(batches) * node_budget)
    _reconcile_oversize(outcomes, over_status)
    return outs + [o for o in over_outs if o is not None], \
        _rejection_stats(stats, outcomes)


def drain_gnn_queue_sharded(fn, params, queue, node_budget: int,
                            edge_budget: int, batch_graphs: int,
                            num_shards: int, fallback_fn=None,
                            task: str = "graph", *, partition_fn=None,
                            validate: bool = True):
    """Sharded wave drain: requests are partitioned into per-device shard
    waves (data.pipeline.pack_dataset(num_shards=)) and each wave runs
    as one SPMD program over the ("data",) mesh — ``fn`` from
    ``gnn_model.make_sharded_apply``, compiled exactly once. Graph-task
    outputs come back in wave host order (gather_shard_outputs); node
    tasks (``task="node"``) get the raw stacked per-shard node tables
    per wave — their row order is shard-local, so there is no global
    host order to restore. Oversize requests behave exactly as in
    ``drain_gnn_queue`` (same ``_launch_packed`` body: partitioned SPMD
    program first, padded fallback second, explicit rejection last),
    and so do the per-request rejection outcomes (same ``_admit``
    screen)."""
    from repro.core import gnn_model as G
    from repro.data import pipeline as P
    packable, oversize, outcomes = _admit(
        queue, node_budget, edge_budget,
        can_fallback=fallback_fn is not None,
        can_partition=partition_fn is not None, validate=validate)
    waves, leftover = P.pack_dataset(packable, node_budget, edge_budget,
                                     batch_graphs, num_shards=num_shards)
    assert not leftover, "_admit already screened for budget fit"
    dev_outs, over_outs, over_status, stats = _launch_packed(
        lambda w: fn(params, G.stack_shards(w)), waves, oversize,
        None if fallback_fn is None else (lambda el: fallback_fn(params, el)),
        partition_fn=partition_fn,
        graphs_in=lambda w: w.n_graphs,
        slots_in=lambda w: sum(int((b["node_graph_id"]
                                    < batch_graphs).sum())
                               for b in w.shards),
        slot_capacity=len(waves) * num_shards * node_budget)
    stats["num_shards"] = num_shards
    _reconcile_oversize(outcomes, over_status)
    if task == "graph":
        outs = [P.gather_shard_outputs(np.asarray(o), w.index)
                for w, o in zip(waves, dev_outs)]
    else:
        outs = dev_outs
    return outs + [o for o in over_outs if o is not None], \
        _rejection_stats(stats, outcomes)


def _partition_or_infeasible(partition_fn, g):
    """Adapt the wave drains' graph -> output-or-None partition callable
    to the continuous scheduler's executor protocol, where infeasibility
    is the explicit ``PartitionInfeasible`` routing signal."""
    from repro.runtime import scheduler as S
    out = partition_fn(g)
    if out is None:
        raise S.PartitionInfeasible(
            f"{g.num_nodes} nodes/{g.num_edges} edges cannot split under "
            "the per-device budgets")
    return out


def drain_gnn_queue_continuous(fn, params, queue, node_budget: int,
                               edge_budget: int, batch_graphs: int,
                               fallback_fn=None, *, partition_fn=None,
                               load_graphs_per_s: float = 512.0,
                               deadline_s: float = 0.05,
                               max_queue_depth: int = 1024,
                               launch_timeout_s: float = float("inf"),
                               max_retries: int = 2,
                               validate: bool = True,
                               seed: int = 0):
    """Continuous-batching drain (``runtime.scheduler``): the queue is
    replayed as an open-loop Poisson arrival process at
    ``load_graphs_per_s`` on the scheduler's virtual clock, while each
    launch's service time is the *measured* wall-seconds of the real
    packed program (``MeasuredExecutor``) — so the p50/p99 latency
    statistics are traffic-shaped, the compute cost is real, and the
    outputs are the real program's outputs (parity with the wave
    drain). Batches launch on deadline expiry or budget-full; oversize
    requests ride ``partition_fn`` (the intra-graph partitioned SPMD
    program; raise ``scheduler.PartitionInfeasible`` inside it to
    reroute a graph to the oracle) then ``fallback_fn``; admissions
    beyond ``max_queue_depth``
    (or malformed graphs, when ``validate``) are rejected explicitly.
    The fault-tolerance knobs ride through: a launch not complete
    within ``launch_timeout_s`` of virtual time fails as a hang and its
    requests re-pack onto healthy lanes, up to ``max_retries`` times
    each before the dead-letter ``failed`` status (docs/SERVING.md
    §Fault tolerance). Returns (responses, stats) — ``responses`` are
    ``runtime.scheduler.Response`` records carrying per-request outputs
    and latencies. Lifecycle: docs/SERVING.md."""
    from repro.core import gnn_model as G
    from repro.runtime import scheduler as S
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xA221]))
    t = 0.0
    trace = []
    for g in queue:
        t += float(rng.exponential(1.0 / load_graphs_per_s))
        trace.append((t, g, "default"))
    executor = S.MeasuredExecutor(
        batch_fn=lambda b: np.asarray(jax.block_until_ready(
            fn(params, G.packed_to_device(b)))),
        fallback_fn=None if fallback_fn is None else (lambda g: np.asarray(
            jax.block_until_ready(fallback_fn(params, _fallback_input(g))))),
        partition_fn=None if partition_fn is None else (
            lambda g: np.asarray(jax.block_until_ready(
                _partition_or_infeasible(partition_fn, g)))))
    sched = S.ContinuousScheduler(
        S.SchedulerConfig(node_budget, edge_budget, batch_graphs,
                          max_queue_depth=max_queue_depth,
                          default_tier=S.SLOTier("standard", deadline_s, 1),
                          launch_timeout_s=launch_timeout_s,
                          max_retries=max_retries, validate=validate),
        executor)
    S.run_trace(sched, trace)
    stats = sched.summary()
    stats["n_batches"] = stats["n_launches"]
    stats["offered_load_graphs_per_s"] = load_graphs_per_s
    stats["deadline_s"] = deadline_s
    return sched.responses, stats


def gnn_model_config(args):
    """The model ``--gnn`` serves: the paper widths
    (``configs.gnn.benchmark_config``) or, with ``--reduced``, the toy
    config, under the CLI's dataflow and precision."""
    from repro.configs.gnn import DATASETS, config as gnn_config
    cfg = gnn_config(args.conv, reduced=args.reduced)
    return dataclasses.replace(
        cfg, gnn_dataflow=args.dataflow,
        avg_degree=float(DATASETS["qm9"].avg_degree),
        gnn_precision=args.precision)


def gnn_main(args):
    from repro.configs.gnn import DATASETS
    from repro.core import aggregations as agg_mod
    from repro.core import gnn_model as G
    from repro.data import pipeline as P

    # single-device serving may opt into the fused Pallas segment kernel
    # (Mosaic-compiled on TPU, interpreted elsewhere — resolved by the
    # aggregation defaults); the default stays XLA, the safe choice under
    # pjit and on CPU hosts
    agg_mod.set_default_backend(args.agg_backend)
    cfg = gnn_model_config(args)
    ds = DATASETS["qm9"]
    params = prm.materialize(G.model_plan(cfg), jax.random.key(0))
    queue = [P.make_graph(ds, i) for i in range(args.requests)]
    node_budget = P.size_budget(args.batch_graphs, ds.avg_nodes)
    edge_budget = P.size_budget(args.batch_graphs,
                                ds.avg_nodes * ds.avg_degree)
    if args.oversize_requests > 0:
        # giant-graph traffic: requests that exceed the packed budgets
        # and exercise the oversize lifecycle (partitioned mesh program,
        # else padded oracle — docs/SERVING.md). 1.2x the node budget
        # keeps ceil(n/P) owned rows + the BFS-frontier halo inside the
        # per-device budget even on a 2-device mesh
        big_cfg = dataclasses.replace(
            ds, avg_nodes=int(1.2 * node_budget),
            max_nodes=max(ds.max_nodes, 4 * node_budget),
            max_edges=max(ds.max_edges, 4 * edge_budget),
            seed=ds.seed + 0x0B1)
        queue += [P.make_graph(big_cfg, i)
                  for i in range(args.oversize_requests)]
    # precision datapath: resolve the policy once; int8 grids are
    # max-abs calibrated on the warmup window. Oversize requests can't
    # ride a GraphBatch (pack_graphs would raise on them) — they are
    # excluded from the calibration batch and still get served through
    # the padded fallback below.
    warm = queue[:args.batch_graphs]
    warm_fit = [g for g in warm
                if P.graph_fits_budget(g, node_budget, edge_budget)]
    warm_batch = None
    if warm_fit:
        warm_batch, _ = P.pack_graphs(warm_fit, node_budget, edge_budget,
                                      args.batch_graphs)
        policy = G.calibrated_policy(params, cfg,
                                     G.packed_to_device(warm_batch))
    else:   # nothing packable to calibrate on: uncalibrated grids
        policy = G.resolve_policy(cfg)
    fn = jax.jit(lambda p, b: G.apply_packed(p, cfg, b, None, policy))
    # oversize requests fall back to the padded per-graph oracle so every
    # request is answered, not silently dropped
    fallback_fn = jax.jit(lambda p, el: G.apply(p, cfg, el, None, policy))

    # mesh-aware oversize routing: with >= 2 local devices, oversize
    # graphs split across the whole device pool and run through the
    # partitioned SPMD program (apply_packed_partitioned); the padded
    # oracle stays as the no-mesh fallback and the escape hatch for
    # graphs the partitioner cannot split under the per-device budgets
    partition_fn = None
    n_dev = len(jax.devices())
    if n_dev >= 2:
        from repro.launch.mesh import make_data_mesh
        part_mesh = make_data_mesh(n_dev)

        def partition_fn(g):
            try:
                part = P.partition_graph(g, n_dev, node_budget,
                                         edge_budget)
            except ValueError:
                return None
            return G.apply_packed_partitioned(params, cfg, part,
                                              part_mesh, None, policy)

    if args.scheduler == "continuous" and args.shards > 1:
        raise SystemExit("--scheduler continuous drives a single-host "
                         "executor; drop --shards or use --scheduler wave")

    if args.shards > 1:
        # data-parallel sharded drain: waves of per-device shards over a
        # ("data",) mesh, params replicated, one SPMD program
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh(args.shards)
        sharded_fn = G.make_sharded_apply(cfg, mesh, None, policy)

        def drain(q):
            return drain_gnn_queue_sharded(
                sharded_fn, params, q, node_budget, edge_budget,
                args.batch_graphs, args.shards, fallback_fn,
                task=cfg.task, partition_fn=partition_fn)
    else:
        def drain(q):
            return drain_gnn_queue(fn, params, q, node_budget,
                                   edge_budget, args.batch_graphs,
                                   fallback_fn, partition_fn=partition_fn)

    # warmup: compile the single fixed-shape program
    _, _ = drain(warm)

    if args.scheduler == "continuous":
        # continuous batching: open-loop Poisson arrivals on the virtual
        # clock, measured service times, deadline/budget-full launches
        _, stats = drain_gnn_queue_continuous(
            fn, params, queue, node_budget, edge_budget,
            args.batch_graphs, fallback_fn, partition_fn=partition_fn,
            load_graphs_per_s=args.load, deadline_s=args.deadline_ms / 1e3,
            max_queue_depth=args.queue_depth,
            launch_timeout_s=(args.launch_timeout_ms / 1e3
                              if args.launch_timeout_ms > 0
                              else float("inf")),
            max_retries=args.max_retries)
        stats["precision"] = policy.name

        def ms(v):          # None when served == 0 — print it honestly
            return "n/a" if v is None else f"{v * 1e3:.1f} ms"
        print(f"conv={args.conv} precision={policy.name} continuous "
              f"scheduler served {stats['served']}/{len(queue)} graphs in "
              f"{stats['n_batches']} launches at "
              f"{args.load:.0f} offered graphs/s "
              f"(p50 {ms(stats['p50_latency_s'])}, "
              f"p99 {ms(stats['p99_latency_s'])}, batch fill "
              f"{stats['mean_batch_fill'] * 100:.0f}%, sustained "
              f"{stats['graphs_per_s']:.0f} graphs/s, "
              f"{stats['partitioned_served']} oversize via partitioned "
              f"mesh, "
              f"{stats['fallback_served']} oversize via padded fallback, "
              f"{stats['rejected_queue_full']} rejected by backpressure, "
              f"{stats['rejected_invalid']} invalid, "
              f"{stats['failed']} failed after retries)")
        return stats
    _, stats = drain(queue)
    stats["precision"] = policy.name
    stats["compute_bytes"] = policy.compute_bytes
    if not policy.is_fp32 and warm_batch is not None:
        # per-precision parity: output error of the low-precision program
        # vs the fp32 program on the warmup batch (pin an explicit fp32
        # policy — cfg.gnn_precision must not leak into the reference)
        from repro.core import quantization as Q
        fp32 = Q.resolve_policy("fp32", cfg.gnn_num_layers)
        dev = G.packed_to_device(warm_batch)
        ref = jax.jit(lambda p, b: G.apply_packed(
            p, cfg, b, None, fp32))(params, dev)
        got = fn(params, dev)
        k = int(warm_batch["num_graphs"])
        stats["output_error_vs_fp32"] = Q.error_stats(
            np.asarray(got)[:k], np.asarray(ref)[:k])
    err = stats.get("output_error_vs_fp32")
    err_txt = "" if err is None else \
        f", |err vs fp32| max {err['max_abs']:.2e} " \
        f"(SQNR {err['sqnr_db']:.0f} dB)"
    shards_txt = "" if args.shards <= 1 else \
        f" over {args.shards} device shards"
    print(f"conv={args.conv} precision={policy.name} served "
          f"{stats['served']} graphs in "
          f"{stats['n_batches']} packed batches{shards_txt} "
          f"({stats['graphs_per_s']:.0f} graphs/s, node-slot utilization "
          f"{stats['node_slot_utilization'] * 100:.0f}%, "
          f"{stats['partitioned_served']} oversize via partitioned mesh, "
          f"{stats['fallback_served']} oversize via padded fallback, "
          f"{stats['rejected_oversize']} rejected oversize, "
          f"{stats['rejected_invalid']} rejected invalid){err_txt}")
    return stats


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHS), default="qwen3-8b")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the toy-width config (LM: the reduced "
                         "arch; --gnn: hidden 16 / out 8) instead of the "
                         "full one (--gnn: configs.gnn.benchmark_config, "
                         "the paper's §VIII-B widths)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--gnn", action="store_true",
                    help="serve packed GraphBatch GNN inference")
    ap.add_argument("--conv", default="gcn",
                    choices=list(Cv.CONV_TYPES))
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--oversize-requests", type=int, default=0,
                    help="append N giant graphs (~2x the node budget) to "
                         "the --gnn queue to exercise the oversize "
                         "lifecycle: partitioned SPMD program on a >= "
                         "2-device mesh, padded per-graph oracle "
                         "otherwise (docs/SERVING.md)")
    ap.add_argument("--batch-graphs", type=int, default=32)
    ap.add_argument("--agg-backend", default="xla",
                    choices=["xla", "pallas"],
                    help="segment-aggregation backend for --gnn serving "
                         "(pallas = fused edge-block kernel, single-device)")
    ap.add_argument("--dataflow", default="auto",
                    choices=["auto", "aggregate_first", "transform_first"],
                    help="transform/aggregate ordering for linear convs "
                         "(auto = per-layer cost model)")
    ap.add_argument("--precision", default="fp32",
                    choices=["fp32", "bf16", "int8"],
                    help="PrecisionPolicy datapath for --gnn serving "
                         "(low-precision tiles, fp32 accumulation; int8 "
                         "grids calibrated on the warmup batch)")
    ap.add_argument("--scheduler", default="wave",
                    choices=["wave", "continuous"],
                    help="--gnn queue discipline: 'wave' drains the whole "
                         "queue through synchronous packed waves (offline "
                         "throughput baseline); 'continuous' replays it as "
                         "an open-loop Poisson arrival process through the "
                         "continuous-batching scheduler "
                         "(runtime.scheduler, docs/SERVING.md)")
    ap.add_argument("--load", type=float, default=512.0,
                    help="offered load in graphs/s for --scheduler "
                         "continuous (open-loop Poisson arrivals)")
    ap.add_argument("--deadline-ms", type=float, default=50.0,
                    help="max queue wait before a partially-filled batch "
                         "launches (--scheduler continuous; the "
                         "latency/throughput knob)")
    ap.add_argument("--queue-depth", type=int, default=1024,
                    help="pending-queue bound for --scheduler continuous; "
                         "admissions beyond it are rejected (backpressure)")
    ap.add_argument("--launch-timeout-ms", type=float, default=0.0,
                    help="per-launch virtual-time bound for --scheduler "
                         "continuous: a launch not complete within it "
                         "fails as a hang and its requests re-pack onto "
                         "healthy lanes (0 = disabled)")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="failed-launch re-pack attempts per request for "
                         "--scheduler continuous before the explicit "
                         "dead-letter 'failed' status")
    ap.add_argument("--shards", type=int, default=1,
                    help="data-parallel device shards for --gnn serving: "
                         "the queue drains into per-device packed shard "
                         "waves over a ('data',) mesh (needs >= N "
                         "devices; on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count)")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    compile_cache.enable()

    if args.gnn:
        stats = gnn_main(args)
        if stats.get("failed"):
            raise SystemExit(
                f"{stats['failed']} requests failed after retries; first "
                f"error: {stats.get('first_error')}")
        return

    cfg = get_config(args.arch, reduced=args.reduced)
    plan = lm.model_plan(cfg)
    params = prm.materialize(plan, jax.random.key(0))
    b, pl_, total = args.batch, args.prompt_len, args.prompt_len + args.gen

    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (b, pl_)), jnp.int32)
    mem = None
    if cfg.family == "vlm":
        mem = jnp.zeros((b, cfg.num_mem_tokens, cfg.mem_dim), jnp.bfloat16)
    if cfg.family == "audio":
        mem = jnp.zeros((b, total, cfg.d_model), jnp.bfloat16)

    mem_len = total if cfg.family == "audio" else cfg.num_mem_tokens
    cplan = lm.cache_plan(cfg, b, total, mem_len=mem_len)
    caches = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), prm.abstract(cplan))

    logits, pref_caches = jax.jit(
        lambda p, ids: lm.prefill(p, cfg, ids, mem))(params, prompts)
    caches = pad_caches(pref_caches, caches)

    decode = jax.jit(
        lambda p, c, ids, pos: lm.decode_step(p, cfg, c, ids, pos),
        donate_argnums=(1,))
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    out_tokens = [tok]
    t0 = time.perf_counter()
    for i in range(args.gen):
        logits, caches = decode(params, caches, tok, jnp.int32(pl_ + i))
        tok = jnp.argmax(logits[:, 0], axis=-1)[:, None].astype(jnp.int32)
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    dt = time.perf_counter() - t0
    gen = np.concatenate([np.asarray(t) for t in out_tokens], axis=1)
    print(f"arch={cfg.name} generated {gen.shape} tokens "
          f"({args.gen * b / dt:.1f} tok/s total, "
          f"{dt / args.gen * 1e3:.1f} ms/step)")
    print("sample:", gen[0, :16].tolist())


if __name__ == "__main__":
    main()
