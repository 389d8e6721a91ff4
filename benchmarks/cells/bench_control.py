#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

  python3 benchmarks/cells/bench_control.py --workload gcn-qm9.screen \
      --seeds 11,12,13 --seconds 3

For each seed, in one process: the cell's driver runs the program for a
short window at the cell's own sizes and load, and its answers are
compared with the reference (the sound reading). Then two controls are
read on the same requests:

* ``ref_bf16x3``: the reference computed with its weight products at
  ``bf16x3`` (a TPU's ``high`` precision, the step below the ``highest``
  that the configuration states) put in the program's place;
* ``program_bf16``: the program itself with its own ``bf16`` precision
  policy switched on, through the same driver.

Prints one JSON line per seed with ``max_err`` of each, and a summary
line. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import bench_harness as H  # noqa: E402
import bench_molecules  # noqa: E402
import bench_run as R  # noqa: E402


def reading(cell: H.Cell, config: dict, seed: int, seconds: float,
            require_chip: bool = True) -> dict:
    """Drive the program of ``config`` through the cell's traffic and
    return its answers with the pool they came from."""
    traffic = cell.traffic
    pool_mols = bench_molecules.make_pool(config["molecules"], seed,
                                          traffic["pool_graphs"])
    pool = [H.to_graph(m) for m in pool_mols]
    sut = H.Sut(config, H.make_weights(config["model"], seed),
                shards=int(traffic.get("shards", 1)))
    if traffic["mode"] == "screen":
        H.screen_warmup(sut, pool, traffic, seed)
        ans = H.screen_answers(H.run_screen(sut, pool, traffic, seed,
                                            seconds))
    else:
        H.online_warmup(sut, pool, traffic, seed)
        ans = H.online_answers(H.run_online(sut, pool, traffic, seed,
                                            seconds))
    H.free_device_state(sut)
    return {"ans": ans, "pool": pool_mols}


def seed_readings(cell: H.Cell, seed: int, seconds: float,
                  require_chip: bool = True) -> dict:
    config = cell.config
    limits = config["check"]
    sound = reading(cell, config, seed, seconds, require_chip)
    ans, pool = sound["ans"], sound["pool"]
    ref = H.reference_for(config, seed, pool, ans["idx"])
    every = dict(limits, max_err=0.0, rms_err=0.0)

    def errs(a, r):
        c = H.compare(a, r, every)
        return c["max_err"]["value"], c["rms_err"]["value"]

    out = {"seed": seed, "served": ans["served"]}
    out["sound"], out["sound_rms"] = errs(ans, ref)
    low = H.reference_for(config, seed, pool, ans["idx"], "bf16x3")
    ctrl = dict(ans, rows=[low[int(i)] for i in ans["idx"]])
    out["ref_bf16x3"], out["ref_bf16x3_rms"] = errs(ctrl, ref)
    bf16 = copy.deepcopy(config)
    bf16["precision"]["program"] = "bf16"
    prog = reading(cell, bf16, seed, seconds, require_chip)["ans"]
    ref_b = H.reference_for(config, seed, pool, prog["idx"])
    out["program_bf16"], out["program_bf16_rms"] = errs(prog, ref_b)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    cell = H.load_cell(args.workload)
    R.configure_jax(cell.config)
    R.devices_for(cell.chips, True)
    rows = []
    for s in args.seeds.split(","):
        t0 = time.perf_counter()
        r = seed_readings(cell, H.norm_seed(int(s)), args.seconds)
        r["wall_s"] = time.perf_counter() - t0
        rows.append(r)
        print(json.dumps(r), flush=True)
    print(json.dumps({
        "workload": args.workload, "seeds": len(rows),
        "sound_max": max(r["sound"] for r in rows),
        "sound_rms_max": max(r["sound_rms"] for r in rows),
        "ref_bf16x3_min": min(r["ref_bf16x3"] for r in rows),
        "ref_bf16x3_rms_min": min(r["ref_bf16x3_rms"] for r in rows),
        "program_bf16_min": min(r["program_bf16"] for r in rows)}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
