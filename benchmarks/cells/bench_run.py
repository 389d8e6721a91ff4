"""One run of one cell: set-up, the measured window, the check, the
result. ``run.py`` is its command line; ``bench_control`` and
``bench_knee`` reuse the set-up."""
from __future__ import annotations

import gc
import json
import shutil
import tempfile
import time
from pathlib import Path

import bench_harness as H
import bench_trace

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def configure_jax(config: dict):
    import jax
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_default_matmul_precision",
                      config["precision"]["matmul_precision"])


class CompileCounter:
    """Programs the process compiles or loads from the persistent cache,
    counted from JAX's own monitoring events: none may fall in the
    measured window."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name: str, **_):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.count += 1

    def _duration(self, name: str, _secs: float, **_):
        if name == "/jax/core/compile/jaxpr_trace_duration":
            self.count += 1


_COUNTER: list = []


def compile_counter() -> CompileCounter:
    if not _COUNTER:
        _COUNTER.append(CompileCounter())
    return _COUNTER[0]


def devices_for(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise SystemExit(f"run.py: JAX found no TPU (platform "
                         f"{devs[0].platform!r}); nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"run.py: the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def memory_peak(devs) -> int:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def peak_of(kind: str, require_chip: bool) -> dict | None:
    peaks = json.loads((HERE / "peaks.json").read_text())
    if kind in peaks:
        return peaks[kind]
    if require_chip:
        raise SystemExit(f"run.py: no peaks for device kind {kind!r} in "
                         "peaks.json")
    return None


def run_cell(cell: H.Cell, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, t_start: float) -> dict:
    import jax
    used = devices_for(cell.chips, require_chip)
    configure_jax(cell.config)
    kind = used[0].device_kind
    peak = peak_of(kind, require_chip)
    config, traffic, family = cell.config, cell.traffic, cell.family
    seed_n = H.norm_seed(seed)

    pool_mols = family.make_pool(config, seed_n, traffic["pool_graphs"])
    pool = [H.to_graph(m) for m in pool_mols]
    params = H.make_weights(family, config["model"], seed_n)
    sut = H.Sut(family, config, params,
                shards=int(traffic.get("shards", 1)))
    if traffic["mode"] == "screen":
        H.screen_warmup(sut, pool, traffic, seed_n)
        driver, answers = H.run_screen, H.screen_answers
    elif traffic["mode"] == "online":
        H.online_warmup(sut, pool, traffic, seed_n)
        driver, answers = H.run_online, H.online_answers
    else:
        raise SystemExit(f"run.py: unknown traffic mode {traffic['mode']!r}")
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    # the heap that imports and set-up left (JAX, numpy, the program,
    # the traffic pool) is frozen out of the collector: a collection in
    # the window then walks only what the window made
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start

    counter = compile_counter()
    compiles = counter.count
    with H.span(bench_trace.WINDOW_SPAN):
        rec = driver(sut, pool, traffic, seed_n, seconds)
    compiles = counter.count - compiles
    if trace:
        jax.profiler.stop_trace()
    device = {"platform": used[0].platform, "kind": kind,
              "count": len(used), "memory_peak_bytes": memory_peak(used)}
    ans = answers(rec)
    del rec, params
    gc.unfreeze()
    H.free_device_state(sut)

    t_ref = time.perf_counter()
    ref = H.reference_for(family, config, seed_n, pool_mols, ans["idx"])
    check = H.compare(ans, ref, config["check"])
    reference_s = time.perf_counter() - t_ref
    result = {"correct": H.passes(check), "attempted": ans["attempted"],
              "failed": ans["unserved"] + ans["lost"]}
    if trace:
        reduced = bench_trace.reduce_events(
            bench_trace.load_events(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        flops = H.served_flops(family, config["model"], pool_mols,
                               ans["idx"])
        view = H.RunView(cell, ans, reduced, flops, peak or {}, len(used))
        result["metrics"] = H.per_layer(view)
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
        result["device"] = device
        if reduced is not None:
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
    else:
        result["metrics"] = H.end_to_end(cell, ans, setup_s)
        result["device"] = device
    result["compiles_in_window"] = compiles
    result["reference_s"] = reference_s
    result["check"] = check
    return result
