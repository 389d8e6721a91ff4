"""Paper Fig. 4: direct-fit performance-model accuracy.

Builds a database of synthesized designs (XLA compile + report = the
Vitis-HLS synthesis analogue), fits the RF latency and memory models, and
reports 5-fold CV MAPE — the paper's numbers are ~36 % (latency) and
~17-18 % (BRAM). Latency target = modeled roofline latency of the compiled
artifact; with --measured the target is the *measured* testbench runtime
(noisier — closer to the paper's HLS-report target).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from repro.core import dse
from repro.core import perf_model as PM

RESULTS = os.path.join(os.path.dirname(__file__), "results")


def run(n: int = 400, seed: int = 0, measured: bool = False,
        log=print) -> dict:
    os.makedirs(RESULTS, exist_ok=True)
    db_path = os.path.join(RESULTS, f"design_db_{n}_{int(measured)}.json")
    if os.path.exists(db_path):
        with open(db_path) as f:
            db = json.load(f)
        if log:
            log(f"loaded cached design DB ({len(db)} designs)")
    else:
        t0 = time.time()
        db = dse.build_database(n, "/tmp/gnnb_dse", seed=seed,
                                run_testbench=measured, log=log)
        if log:
            log(f"synthesized {n} designs in {time.time() - t0:.0f}s")
        with open(db_path, "w") as f:
            json.dump(db, f)

    x = np.stack([PM.features(d) for d in db])
    lat_key = "measured_ms" if measured else "latency_s"
    y_lat = np.array([d[lat_key] for d in db])
    y_mem = np.array([d["hbm_bytes"] for d in db])

    res = {
        "n_designs": len(db),
        "latency_cv_mape": PM.kfold_cv_mape(x, y_lat, k=5),
        "memory_cv_mape": PM.kfold_cv_mape(x, y_mem, k=5),
        "latency_target": lat_key,
        "paper_latency_mape": 36.0,
        "paper_bram_mape": 17.5,
    }
    with open(os.path.join(RESULTS, "perf_model_accuracy.json"), "w") as f:
        json.dump(res, f, indent=1)
    if log:
        log(f"latency CV-MAPE {res['latency_cv_mape']:.1f}% "
            f"(paper ~36%), memory CV-MAPE {res['memory_cv_mape']:.1f}% "
            f"(paper ~17.5%)")
    return res


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=400)
    ap.add_argument("--measured", action="store_true")
    args = ap.parse_args()
    run(args.n, measured=args.measured)
