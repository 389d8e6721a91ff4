#!/usr/bin/env python3
"""Run-to-run spread of a cell's metrics, the readings the bounds are set
from.

  python3 benchmarks/cells/bench_spread.py --workload gcn-qm9.screen \
      --seeds 11,12,13,14,15,16 --sets 2 --seconds 30 [--out runs.jsonl]

Runs ``run.py`` once per seed, for each of ``--sets`` sets with the same
seeds, one process after another (this parent never touches JAX, so each
child has the chips to itself). Prints each run's result line, then per
metric the median and the spread of each set (quartile distance over the
median, ``bench_stats.spread``) and the wider of them, and the largest
``max_err`` that any run read.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import bench_stats  # noqa: E402


def one_run(workload: str, seed: int, seconds: float, trace: int) -> dict:
    p = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace",
         str(trace)], cwd=ROOT, capture_output=True, text=True,
        timeout=900)
    lines = p.stdout.strip().splitlines()
    if p.returncode != 0 or not lines:
        return {"seed": seed, "rc": p.returncode, "error": p.stderr[-2000:]}
    out = json.loads(lines[-1])
    out["seed"] = seed
    out["rc"] = p.returncode
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    sets = []
    for k in range(args.sets):
        runs = []
        for s in seeds:
            r = one_run(args.workload, s, args.seconds, args.trace)
            r["set"] = k
            runs.append(r)
            print(json.dumps(r), flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(r) + "\n")
        sets.append(runs)
    summary = {"workload": args.workload, "seconds": args.seconds,
               "metrics": {}}
    names = sorted({m for runs in sets for r in runs
                    for m in r.get("metrics", {})})
    for m in names:
        per = []
        for runs in sets:
            v = [r["metrics"][m]["value"] for r in runs
                 if m in r.get("metrics", {})
                 and r["metrics"][m]["value"] is not None]
            if len(v) >= 2:
                per.append({"median": statistics.median(v),
                            "spread": bench_stats.spread(v)})
        summary["metrics"][m] = {
            "sets": per, "widest": max((p["spread"] for p in per),
                                       default=None)}
    every = [r for runs in sets for r in runs]
    summary["correct"] = sum(1 for r in every if r.get("correct"))
    summary["runs"] = len(every)
    summary["max_err_max"] = max(
        (r["check"]["max_err"]["value"] for r in every if "check" in r),
        default=None)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
