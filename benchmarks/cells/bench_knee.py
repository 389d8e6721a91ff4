#!/usr/bin/env python3
"""One sweep of offered rates through an online cell, to find its knee.

  python3 benchmarks/cells/bench_knee.py --workload gcn-qm9.online \
      --rates 2000,4000,6000 --seconds 6 --seed 5

In one process, for each rate in turn, the cell's open-loop driver runs
for ``--seconds`` and prints one JSON line: offered and served rates,
the latency median and tail over the whole window and over each half of
it (a tail that grows from the first half to the second is a queue that
grows), how late the generator ran, and the mean batch fill. The knee is
the highest rate that is served in full with no growing queue; the cell
then offers a fixed share of it (``traffic/<name>.json``). The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import bench_harness as H  # noqa: E402
import bench_stats  # noqa: E402
import bench_run as R  # noqa: E402


def sweep_point(sut, pool, traffic, seed: int, rate: float,
                seconds: float) -> dict:
    rec = H.run_online(sut, pool, dict(traffic, rate_per_s=rate), seed,
                       seconds)
    ans = H.online_answers(rec)
    lat = ans["latency_s"]
    half = len(lat) // 2

    def ms(v, q):
        p = bench_stats.percentile(v, q)
        return None if p is None else p * 1e3

    return {"rate_per_s": rate, "attempted": ans["attempted"],
            "served": ans["served"], "failed": ans["unserved"] + ans["lost"],
            "served_per_s": ans["served"] / max(ans["window_s"], 1e-9),
            "p50_ms": ms(lat, 50), "p90_ms": ms(lat, 90),
            "p95_ms": ms(lat, 95), "p99_ms": ms(lat, 99),
            "max_ms": ms(lat, 100),
            "slow_launches": sum(1 for x in set(zip(
                [r.batch_seq for r in rec["responses"]],
                [r.complete_s - r.launch_s for r in rec["responses"]]))
                if x[1] > 0.05),
            "p99_ms_first_half": ms(lat[:half], 99),
            "p99_ms_second_half": ms(lat[half:], 99),
            "gen_lag_p99_ms": ms(ans["gen_lag_s"], 99),
            "launches": ans["launches"],
            "mean_fill": ans["served"] / max(ans["launches"], 1)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--repeat", type=int, default=1,
                    help="windows per rate, one after another")
    args = ap.parse_args(argv)
    cell = H.load_cell(args.workload)
    R.configure_jax(cell.config)
    R.devices_for(cell.chips, True)
    seed = H.norm_seed(args.seed)
    config, traffic, family = cell.config, cell.traffic, cell.family
    pool_mols = family.make_pool(config, seed, traffic["pool_graphs"])
    pool = [H.to_graph(m) for m in pool_mols]
    sut = H.Sut(family, config, H.make_weights(family, config["model"], seed))
    H.online_warmup(sut, pool, traffic, seed)
    gc.collect()
    gc.freeze()
    for rate in (float(r) for r in args.rates.split(",")):
        for _ in range(args.repeat):
            print(json.dumps(sweep_point(sut, pool, traffic, seed, rate,
                                         args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
