"""CPU tests of the per-layer metrics read from the program's own spans
and counters (``bench_spans`` and the readers in ``metrics/`` whose
``source`` is ``program_span``): their entries in ``BENCHMARK.json``,
their readings of a made-up snapshot, nothing read from a program
without the tracer, and a traced run that reports them."""
from __future__ import annotations

import re
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (str(ROOT / "src"), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

import bench_harness as H  # noqa: E402
import bench_run  # noqa: E402
import bench_spans  # noqa: E402

BENCH = H.load_benchmark()
CELLS = {w["name"]: w for w in BENCH["workloads"]}
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
LAYERS = {m["name"]: m for m in BENCH["per_layer"]}
SCREEN = ["gcn-qm9.screen", "pna-qm9.screen", "gcn-qm9.screen-x4"]

# name -> (unit, layer, moves, workloads)
SPAN_METRICS = {
    "admit_ms.screen": ("ms", "serving front", "graphs_per_s", SCREEN),
    "pack_ms.screen": ("ms", "packing", "graphs_per_s", SCREEN),
    "put_ms.screen": ("ms", "executor", "graphs_per_s", SCREEN),
    "dispatch_ms.screen": ("ms", "executor", "graphs_per_s", SCREEN),
    "wait_ms.screen": ("ms", "executor", "graphs_per_s", SCREEN),
    "gather_ms.screen": ("ms", "packing", "graphs_per_s",
                         ["gcn-qm9.screen-x4"]),
    "submit_us.online": ("us", "scheduler", "p90_ms", ["gcn-qm9.online"]),
    "scan_len.online": ("requests", "scheduler", "p90_ms",
                        ["gcn-qm9.online"]),
    "pack_ms.online": ("ms", "packing", "p90_ms", ["gcn-qm9.online"]),
    "put_max_ms.online": ("ms", "executor", "p90_ms", ["gcn-qm9.online"]),
}

SNAP = {
    "spans": {
        "serve.drain": {"n": 2, "total_s": 0.2, "self_s": 0.002,
                        "max_s": 0.11},
        "serve.admit": {"n": 2, "total_s": 0.04, "self_s": 0.04,
                        "max_s": 0.021},
        "pack.dataset": {"n": 2, "total_s": 0.06, "self_s": 0.06,
                         "max_s": 0.031},
        "device.launch": {"n": 40, "total_s": 0.05, "self_s": 0.03,
                          "max_s": 0.004},
        "device.put": {"n": 40, "total_s": 0.02, "self_s": 0.02,
                       "max_s": 0.0015},
        "device.wait": {"n": 2, "total_s": 0.004, "self_s": 0.004,
                        "max_s": 0.003},
        "pack.gather_shards": {"n": 2, "total_s": 0.008, "self_s": 0.008,
                               "max_s": 0.005},
        "sched.submit": {"n": 1000, "total_s": 0.9, "self_s": 0.05,
                         "max_s": 0.2},
        "pack.graphs": {"n": 50, "total_s": 0.025, "self_s": 0.025,
                        "max_s": 0.001},
    },
    "counters": {"sched.selects": 400, "sched.scanned": 6000},
}

WANT = {
    "admit_ms.screen": 1.0,            # 40 ms of admission / 40 launches
    "pack_ms.screen": 1.5,
    "put_ms.screen": 0.5,
    "dispatch_ms.screen": 0.75,
    "wait_ms.screen": 0.1,
    "gather_ms.screen": 0.2,
    "submit_us.online": 50.0,          # 50 ms of self time / 1000 submits
    "scan_len.online": 15.0,
    "pack_ms.online": 0.5,
    "put_max_ms.online": 1.5,
}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _view(cell="gcn-qm9.screen"):
    return H.RunView(H.load_cell(cell), {"launches": 0}, None, 0.0, {}, 1)


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_span_metric_entry_has_the_benchmarks_shape(name):
    unit, layer, moves, workloads = SPAN_METRICS[name]
    m = LAYERS[name]
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert NAME.match(name) and UNIT.match(m["unit"])
    assert (m["unit"], m["layer"], m["moves"], m["workloads"]) == \
        (unit, layer, moves, workloads)
    assert m["better"] == "lower" and m["source"] == "program_span"
    assert "\n" not in m["layer"] and len(m["layer"]) <= 200
    for w in workloads:
        assert w in CELLS
        assert w in E2E[moves].get("workloads", [w])
        assert name in [x["name"] for x in H.load_cell(w).per_layer]
    assert (HERE / "metrics" / f"{name}.py").is_file()


def test_span_metrics_come_after_the_accepted_ones():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-len(SPAN_METRICS):] == list(SPAN_METRICS)


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_reader_reads_a_made_up_snapshot(name, monkeypatch):
    monkeypatch.setattr(bench_spans, "snapshot", lambda: SNAP)
    assert H.load_reader(name)(_view()) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_reader_reads_nothing_from_an_empty_snapshot(name, monkeypatch):
    monkeypatch.setattr(bench_spans, "snapshot",
                        lambda: {"spans": {}, "counters": {}})
    assert H.load_reader(name)(_view()) is None


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_reader_reads_nothing_without_the_tracer(name, monkeypatch):
    """A program that predates ``repro.runtime.trace``."""
    import repro.runtime
    monkeypatch.setitem(sys.modules, "repro.runtime.trace", None)
    monkeypatch.delattr(repro.runtime, "trace", raising=False)
    assert bench_spans.snapshot() is None
    assert H.load_reader(name)(_view()) is None


# ---------------------------------------------------------- a traced run --
@pytest.fixture
def cpu_jax(monkeypatch):
    """Run cells in this process without the persistent cache, and put
    the matmul precision back afterwards."""
    import jax
    saved = jax.config.jax_default_matmul_precision
    monkeypatch.setattr(
        bench_run, "configure_jax",
        lambda config: jax.config.update(
            "jax_default_matmul_precision",
            config["precision"]["matmul_precision"]))
    yield
    jax.config.update("jax_default_matmul_precision", saved)


@pytest.mark.parametrize("cell", ["gcn-qm9.screen", "gcn-qm9.online"])
def test_traced_run_reports_every_span_metric_of_its_cell(cell, cpu_jax):
    from repro.runtime import trace
    c = H.load_cell(cell)
    c.traffic["pool_graphs"] = 96
    c.traffic["warmup_s"] = 0.2
    if c.traffic["mode"] == "screen":
        c.traffic["chunk_graphs"] = 64
    else:
        c.traffic["rate_per_s"] = 400.0
    trace.reset()
    try:
        result = bench_run.run_cell(c, 2 ** 31 + 17, 0.5, True,
                                    require_chip=False,
                                    t_start=time.perf_counter())
        spans = trace.snapshot()["spans"]
    finally:
        trace.reset()
    assert result["correct"]
    want = {n for n, v in SPAN_METRICS.items() if cell in v[3]}
    assert want and want <= set(result["metrics"])
    for name in want:
        assert result["metrics"][name]["value"] >= 0
    # the profiler ran over the window alone: the warm-up left nothing
    if c.traffic["mode"] == "screen":
        drains = spans["serve.drain"]["n"]
        assert drains * c.traffic["chunk_graphs"] == result["attempted"]
    else:
        assert spans["sched.submit"]["n"] == result["attempted"]
