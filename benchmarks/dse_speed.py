"""Paper Fig. 5: direct-fit model evaluation vs synthesis wall-time.

The paper: 400 Vitis runs ~2 days (9.4 min avg) vs 1.7 ms/model call —
~6 orders of magnitude. Here the synthesis analogue is XLA compile +
report; the model is the fitted RF.
"""
from __future__ import annotations

import json
import math
import os
import time

import numpy as np

from repro.core import dse
from repro.core import perf_model as PM

RESULTS = os.path.join(os.path.dirname(__file__), "results")


def run(n_synth: int = 12, n_model_calls: int = 400, log=print) -> dict:
    os.makedirs(RESULTS, exist_ok=True)
    rng = np.random.default_rng(0)
    synth_times = []
    db = []
    for i in range(n_synth):
        d = dse.sample_design(rng)
        t0 = time.time()
        rec = dse.synthesize_design(d, "/tmp/gnnb_dse_speed")
        synth_times.append(time.time() - t0)
        db.append(rec)
    models = dse.fit_models(db)

    designs = [dse.sample_design(rng) for _ in range(n_model_calls)]
    x = np.stack([PM.features(d) for d in designs])
    models.latency.predict(x[:8])            # warm
    t0 = time.time()
    models.latency.predict(x)
    models.memory.predict(x)
    model_s = time.time() - t0

    synth_avg = float(np.mean(synth_times))
    model_avg = model_s / n_model_calls
    res = {
        "synthesis_avg_s": synth_avg,
        "model_avg_ms": model_avg * 1e3,
        "orders_of_magnitude": math.log10(synth_avg / model_avg),
        "paper_synthesis_avg_s": 9.4 * 60,
        "paper_model_avg_ms": 1.7,
        "paper_orders_of_magnitude": math.log10(9.4 * 60 / 1.7e-3),
    }
    with open(os.path.join(RESULTS, "dse_speed.json"), "w") as f:
        json.dump(res, f, indent=1)
    if log:
        log(f"synthesis {synth_avg:.2f}s/design vs model "
            f"{model_avg * 1e3:.2f}ms/design -> "
            f"{res['orders_of_magnitude']:.1f} orders of magnitude "
            f"(paper: {res['paper_orders_of_magnitude']:.1f})")
    return res


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    run()
