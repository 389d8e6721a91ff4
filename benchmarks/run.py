"""Benchmark harness entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  perf_model_accuracy   (Fig. 4)  derived = latency/memory CV-MAPE
  dse_speed             (Fig. 5)  derived = orders of magnitude
  accelerator_eval      (Tab. IV / Fig. 6) derived = geomean speedups
  resources             (Fig. 7)  derived = utilization headroom
  roofline              (EXPERIMENTS §Roofline) derived = cells ok

Fast CI defaults; REPRO_BENCH_FULL=1 uses the paper-scale settings
(400-design DB etc. — ~40 min on one CPU core).
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(__file__))

FULL = os.environ.get("REPRO_BENCH_FULL", "0") == "1"


def _row(name: str, us: float, derived: str):
    print(f"{name},{us:.1f},{derived}")


def main() -> None:
    from benchmarks import accelerator_eval, dse_speed, \
        perf_model_accuracy, resources, roofline_bench

    log = lambda *a: print("#", *a)

    t0 = time.time()
    r = perf_model_accuracy.run(n=400 if FULL else 60, log=log)
    _row("perf_model_accuracy_fig4", (time.time() - t0) * 1e6,
         f"lat_cv_mape={r['latency_cv_mape']:.1f}%|"
         f"mem_cv_mape={r['memory_cv_mape']:.1f}%|paper=36/17.5")

    t0 = time.time()
    r = dse_speed.run(n_synth=20 if FULL else 8, log=log)
    _row("dse_speed_fig5", (time.time() - t0) * 1e6,
         f"synth={r['synthesis_avg_s']:.2f}s|"
         f"model={r['model_avg_ms']:.2f}ms|"
         f"magnitude={r['orders_of_magnitude']:.1f}")

    t0 = time.time()
    r = accelerator_eval.run(n_graphs=200 if FULL else 24,
                             datasets=None if FULL else
                             ["qm9", "esol", "hiv"], log=log)
    g = r["speedups"]["geomean"]
    _row("accelerator_eval_tab4", (time.time() - t0) * 1e6,
         f"vs_jax_cpu={g['vs_jax_cpu']:.2f}x|"
         f"vs_np_cpu={g['vs_np_cpu']:.2f}x|"
         f"vs_base={g['vs_tpu_base']:.2f}x|paper=6.33/7.08")

    t0 = time.time()
    r = resources.run(log=log)
    _row("resources_fig7", (time.time() - t0) * 1e6,
         f"rows={len(r['rows'])}")

    t0 = time.time()
    r = roofline_bench.run(log=log)
    _row("roofline", (time.time() - t0) * 1e6,
         f"cells={r['cells']}|ok={r.get('ok', 0)}")


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    main()
