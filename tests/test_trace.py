"""Program spans and counters (``runtime.trace``): off without a profiler
session, aggregated with self time under one, written to the profiler's
host plane, and placed at the serving path's layer boundaries."""
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gnn_model as G
from repro.data import pipeline as P
from repro.launch import serve
from repro.nn import param as prm
from repro.runtime import scheduler as S
from repro.runtime import trace

DS = P.GraphDataConfig(avg_nodes=10, max_nodes=64, max_edges=64,
                       node_feat_dim=11, edge_feat_dim=4, seed=5)
CFG = G.GNNModelConfig(
    graph_input_feature_dim=11, graph_input_edge_dim=4, gnn_hidden_dim=16,
    gnn_num_layers=2, gnn_output_dim=8, gnn_conv="gcn",
    mlp_head=G.MLPConfig(in_dim=24, out_dim=1, hidden_dim=8,
                         hidden_layers=1))
NODE_BUDGET, EDGE_BUDGET, MAX_GRAPHS = 64, 128, 4


@pytest.fixture(autouse=True)
def clean_tracer():
    trace.reset()
    yield
    trace.reset()


@pytest.fixture(scope="module")
def program():
    params = prm.materialize(G.model_plan(CFG), jax.random.key(0))
    fn = jax.jit(lambda p, b: G.apply_packed(p, CFG, b))
    return fn, params


def _queue(n=12):
    return [P.make_graph(DS, i) for i in range(n)]


def _drain(program, queue):
    fn, params = program
    return serve.drain_gnn_queue(fn, params, queue, NODE_BUDGET,
                                 EDGE_BUDGET, MAX_GRAPHS)


def _scheduler(program, clock=None):
    fn, params = program
    executor = S.MeasuredExecutor(batch_fn=lambda b: np.asarray(
        jax.block_until_ready(fn(params, G.packed_to_device(b)))))
    return S.ContinuousScheduler(
        S.SchedulerConfig(NODE_BUDGET, EDGE_BUDGET, MAX_GRAPHS,
                          default_tier=S.SLOTier("standard", 0.01, 1),
                          validate=True),
        executor, clock=clock)


def _run_scheduler(sched, queue):
    for g in queue:
        sched.submit(g)
    sched.drain()


def _spans():
    return trace.snapshot()["spans"]


# ---------------------------------------------------------------- off --
def test_off_span_is_the_shared_noop_and_records_nothing(program):
    assert trace.span("serve.drain") is trace.NO_SPAN
    assert trace.span("sched.submit", req_id=3) is trace.NO_SPAN
    with trace.span("outer"):
        trace.count("sched.selects")
    queue = _queue()
    _drain(program, queue)
    _run_scheduler(_scheduler(program), queue)
    assert trace.snapshot() == {"spans": {}, "counters": {}}


# ----------------------------------------------------------------- on --
def test_nested_spans_aggregate_count_self_and_longest(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with trace.span("outer"):
            time.sleep(0.002)
            with trace.span("inner", seq=0):
                time.sleep(0.001)
            with trace.span("inner", seq=1):
                time.sleep(0.005)
                trace.count("hits")
            trace.count("hits", 4)
        with trace.span("outer"):
            pass
    snap = trace.snapshot()
    outer, inner = snap["spans"]["outer"], snap["spans"]["inner"]
    assert outer["n"] == 2 and inner["n"] == 2
    assert outer["self_s"] == pytest.approx(
        outer["total_s"] - inner["total_s"], abs=1e-9)
    assert outer["self_s"] >= 0.002
    assert inner["self_s"] == inner["total_s"]
    assert inner["total_s"] >= 0.006
    assert 0.005 <= inner["max_s"] <= inner["total_s"] - 0.001
    assert outer["max_s"] >= outer["total_s"] / 2
    assert snap["counters"] == {"hits": 5}


def test_reset_clears_the_aggregates(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with trace.span("outer"):
            trace.count("hits")
    assert trace.snapshot()["spans"]
    trace.reset()
    assert trace.snapshot() == {"spans": {}, "counters": {}}


def test_spans_land_in_the_profilers_host_plane(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with trace.span("sched.submit", req_id=7):
            with trace.span("device.put"):
                jnp.ones(4).block_until_ready()
    files = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert files
    data = jax.profiler.ProfileData.from_file(files[0])
    found = {}
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("sched.submit", "device.put"):
                    found[ev.name] = (plane.name, dict(ev.stats))
    assert set(found) == {"sched.submit", "device.put"}
    assert all(p.startswith("/host:") for p, _ in found.values())
    assert found["sched.submit"][1]["req_id"] == 7


# ------------------------------------------------------ serving path --
def test_drain_spans_one_per_stage_and_one_per_batch(program, tmp_path):
    """The drain admits one chunk of ``MAX_GRAPHS`` requests at a time
    inside the packer's pulls: one ``serve.admit`` per chunk, nested in
    ``pack.dataset``, which opens once per batch and once more for the
    pull that finds the queue done. Nothing but the transfer runs inside
    a ``device.launch``."""
    queue = _queue()
    _drain(program, queue)                       # compile off the record
    with jax.profiler.trace(str(tmp_path)):
        _, stats = _drain(program, queue)
    spans = _spans()
    n = stats["n_batches"]
    assert n >= 2
    for name in ("serve.drain", "device.wait"):
        assert spans[name]["n"] == 1, name
    assert spans["serve.admit"]["n"] == -(-len(queue) // MAX_GRAPHS)
    assert spans["pack.dataset"]["n"] == n + 1
    assert spans["device.put"]["n"] == n
    assert spans["device.launch"]["n"] == n
    assert "pack.gather_shards" not in spans
    assert not any(name.startswith("bench.") for name in spans)
    drain = spans["serve.drain"]
    children = sum(spans[k]["total_s"] for k in (
        "pack.dataset", "device.launch", "device.wait"))
    assert drain["self_s"] == pytest.approx(drain["total_s"] - children,
                                            abs=1e-9)
    pack = spans["pack.dataset"]
    assert pack["self_s"] == pytest.approx(
        pack["total_s"] - sum(spans[k]["total_s"] for k in (
            "serve.admit", "pack.attn_counts")), abs=1e-9)
    # device.put runs inside device.launch, and nothing else does: the
    # launch's self time leaves out the transfer alone
    launch = spans["device.launch"]
    assert launch["self_s"] == pytest.approx(
        launch["total_s"] - spans["device.put"]["total_s"], abs=1e-9)


def test_drain_launches_before_the_last_request_is_admitted(
        program, tmp_path, monkeypatch):
    """The first batch is on its way before the queue's last request is
    validated, and ``serve.launch_ahead`` counts every launch but the
    last (each batch here closes at ``MAX_GRAPHS`` graphs)."""
    fn, params = program
    queue = _queue()
    _drain(program, queue)                       # compile off the record
    events = []
    validate = P.validate_graph

    def counting_validate(g):
        events.append("validate")
        return validate(g)

    def counting_fn(p, b):
        events.append("launch")
        return fn(p, b)

    monkeypatch.setattr(P, "validate_graph", counting_validate)
    with jax.profiler.trace(str(tmp_path)):
        _, stats = serve.drain_gnn_queue(counting_fn, params, queue,
                                         NODE_BUDGET, EDGE_BUDGET,
                                         MAX_GRAPHS)
    n = stats["n_batches"]
    assert n == len(queue) // MAX_GRAPHS >= 3
    assert events.count("validate") == len(queue)
    last_validate = len(events) - 1 - events[::-1].index("validate")
    assert events.index("launch") < last_validate
    assert trace.snapshot()["counters"]["serve.launch_ahead"] == n - 1


def test_sharded_drain_spans_the_gather(tmp_path):
    from repro.launch.mesh import make_data_mesh
    shards = 2

    def fn(params, stacked):                     # stand-in for the SPMD
        return jnp.zeros(stacked["graph_valid"].shape + (1,))

    # the stand-in's mesh: this process's one device holds both shards
    fn.mesh = make_data_mesh(1)
    queue = _queue()
    with jax.profiler.trace(str(tmp_path)):
        outs, stats = serve.drain_gnn_queue_sharded(
            fn, None, queue, NODE_BUDGET, EDGE_BUDGET, MAX_GRAPHS, shards)
    spans = _spans()
    n = stats["n_batches"]
    assert sum(len(o) for o in outs) == len(queue)
    for name in ("serve.drain", "serve.admit", "pack.dataset",
                 "device.wait", "pack.gather_shards"):
        assert spans[name]["n"] == 1, name
    assert spans["device.put"]["n"] == n
    assert spans["device.launch"]["n"] == n
    assert trace.snapshot()["counters"]["put.buffers"] == n


def test_put_is_one_buffer_per_launch(program, tmp_path):
    """Each launch opens one ``device.put`` span and sends its packed
    batch as one host-to-device buffer, in the wave drain and under the
    scheduler."""
    queue = _queue()
    _drain(program, queue)                       # compile off the record
    _run_scheduler(_scheduler(program), queue)
    sched = _scheduler(program)
    with jax.profiler.trace(str(tmp_path)):
        _, stats = _drain(program, queue)
        _run_scheduler(sched, queue)
    snap = trace.snapshot()
    launches = stats["n_batches"] + sum(
        1 for l in sched.launches if l["kind"] == "packed")
    assert snap["spans"]["device.put"]["n"] == launches
    assert snap["spans"]["device.launch"]["n"] == stats["n_batches"]
    assert snap["counters"]["put.buffers"] == launches


def test_scheduler_spans_submits_and_launches(program, tmp_path):
    queue = _queue()
    _run_scheduler(_scheduler(program), queue)   # compile off the record
    sched = _scheduler(program)
    with jax.profiler.trace(str(tmp_path)):
        _run_scheduler(sched, queue)
    snap = trace.snapshot()
    spans, counters = snap["spans"], snap["counters"]
    packed = [l for l in sched.launches if l["kind"] == "packed"]
    assert len(packed) >= 2
    assert spans["sched.submit"]["n"] == len(queue)
    assert spans["pack.graphs"]["n"] == len(packed)
    assert spans["exec.run_batch"]["n"] == len(packed)
    assert spans["device.put"]["n"] == len(packed)
    assert counters["sched.selects"] >= len(packed)
    assert counters["sched.scanned"] >= len(queue)


def test_drain_times_admission_and_packing(program, monkeypatch):
    """``total_s`` and ``graphs_per_s`` count the drain from its entry."""
    admit = serve._admit

    def slow_admit(*a, **kw):
        time.sleep(0.05)
        return admit(*a, **kw)

    queue = _queue()
    _drain(program, queue)
    monkeypatch.setattr(serve, "_admit", slow_admit)
    _, stats = _drain(program, queue)
    assert stats["total_s"] >= 0.05
    assert stats["graphs_per_s"] == pytest.approx(
        stats["served"] / stats["total_s"])
