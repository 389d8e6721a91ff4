#!/usr/bin/env python3
"""Chip benchmark of the packed GNN serving path: one run of one cell.

  python3 benchmarks/cells/run.py --workload gcn-qm9.screen --seed 7 \
      --seconds 20 --trace 0

Run from the root of a checkout on a machine that holds the chips the
cell asks for (``BENCHMARK.json``). The run builds the cell's graphs
and weights from ``--seed``, warms up every program the window uses
(that is ``setup_s``, counted from the start of the process), measures
for ``--seconds``, then checks every served answer against the plain
reference. With ``--trace 0`` the result carries the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the profiler and the
result carries the per-layer metrics, the device's busy time and a
breakdown of device ops and idle gaps.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (platform, kind,
count, ``memory_peak_bytes``; with ``--trace 1`` also ``busy_s`` and
``window_s``), with ``--trace 1`` ``breakdown``, and last ``check``, the
numbers compared with their limits, which also end standard error.
Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.

JAX's persistent compilation cache lives in ``.jax_cache`` at the root
of the checkout, and every compile is cached, so only a cell's first run
in a checkout compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import bench_harness as H  # noqa: E402
import bench_run  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = H.load_cell(args.workload)
    result = bench_run.run_cell(cell, args.seed, args.seconds,
                                bool(args.trace), t_start=T_START)
    for name, c in result["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
