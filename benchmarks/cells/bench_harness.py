"""Cells of the chip benchmark: loading by name, the system under test,
the traffic drivers and the output check.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a
configuration (``configs/<name>.json``) under a traffic mix
(``traffic/<name>.json``). The mix's ``mode`` picks the driver:

* ``screen``: closed loop. Successive chunks of requests go through the
  program's wave drain (``serve.drain_gnn_queue``, or
  ``drain_gnn_queue_sharded`` when ``shards`` > 1), one call outstanding,
  until the window ends. Every answer is fetched to the host.
* ``online``: open loop. Poisson arrivals at ``rate_per_s`` are
  submitted into the program's ``ContinuousScheduler`` (one lane, a
  ``MeasuredExecutor`` over the jitted ``apply_packed``), which the
  benchmark drives on a wall clock. A request is timed from when it was
  due to when the benchmark sees its response.

Requests draw, with the seed, from a pool of distinct generated
molecules (``bench_molecules``). Every served request's output row is
checked against the plain reference (``bench_reference``) after the
window; see ``check``.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import re
import time
from pathlib import Path

import numpy as np

import bench_flops
import bench_reference
import bench_stats

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


# ---------------------------------------------------------------- cells --
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reported_in(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(by_name)}")
    wl = by_name[name]
    conf = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{wl['traffic']}.json")
                         .read_text())
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reported_in(m, name, names)]
    return Cell(name, int(wl["chips"]), config, traffic, e2e, per_layer)


def norm_seed(seed: int) -> int:
    """Any whole number as a non-negative seed for numpy's SeedSequence."""
    return int(seed) % (2 ** 63)


def weight_key(seed: int):
    import jax
    words = np.random.SeedSequence([norm_seed(seed), 0x3E16]) \
        .generate_state(2)
    return jax.random.fold_in(jax.random.key(int(words[0])), int(words[1]))


def make_weights(model: dict, seed: int):
    """The model's weights on the device, in one jitted call."""
    import jax
    return jax.jit(lambda k: bench_reference.init_params(model, k))(
        weight_key(seed))


# ---------------------------------------------------- system under test --
def model_config(config: dict):
    """The program's ``GNNModelConfig`` for a configuration file."""
    from repro.core.gnn_model import GNNModelConfig, MLPConfig
    m = config["model"]
    par = m["parallelism"]
    return GNNModelConfig(
        graph_input_feature_dim=m["node_feat_dim"],
        graph_input_edge_dim=m["edge_feat_dim"],
        gnn_hidden_dim=m["hidden_dim"], gnn_num_layers=m["num_layers"],
        gnn_output_dim=m["output_dim"], gnn_conv=m["conv"],
        gnn_activation=m["activation"],
        gnn_skip_connection=m["skip_connection"],
        global_pooling=tuple(m["global_pooling"]),
        mlp_head=MLPConfig(
            in_dim=m["output_dim"] * len(m["global_pooling"]),
            out_dim=m["num_targets"], hidden_dim=m["mlp_hidden_dim"],
            hidden_layers=m["mlp_hidden_layers"],
            activation=m["mlp_activation"], p_in=par["mlp_p_in"],
            p_hidden=par["mlp_p_hidden"], p_out=par["mlp_p_out"]),
        gnn_p_in=par["gnn_p_in"], gnn_p_hidden=par["gnn_p_hidden"],
        gnn_p_out=par["gnn_p_out"], pna_delta=m["pna_delta"],
        gnn_dataflow=m["dataflow"], avg_degree=m["avg_degree"],
        gnn_precision=config["precision"]["program"])


def to_graph(mol: dict):
    from repro.data import pipeline as P
    return P.Graph(mol["node_feat"], mol["edge_index"], mol["edge_feat"],
                   mol["num_nodes"], mol["num_edges"], mol["y"])


@dataclasses.dataclass
class DrainRaw:
    """One wave-drain call as it came back, mapped to rows after the
    window."""
    idx: np.ndarray          # pool index of each request, queue order
    outs: list               # host outputs, one array per batch or wave
    valid: list | None       # graph_valid of each batch (single device)
    outcomes: list           # the drain's per-request statuses
    launches: int


class Sut:
    """The program as a user drives it: a jitted ``apply_packed`` (or the
    sharded program over a ``("data",)`` mesh) behind ``serve``'s wave
    drains, or a ``ContinuousScheduler`` over a ``MeasuredExecutor``."""

    def __init__(self, config: dict, params, shards: int = 1):
        import jax
        from repro.core import gnn_model as G
        sv = config["serving"]
        self.cfg = model_config(config)
        self.node_budget = sv["node_budget"]
        self.edge_budget = sv["edge_budget"]
        self.batch_graphs = sv["batch_graphs"]
        self.serving = sv
        self.shards = shards
        policy = G.resolve_policy(self.cfg)
        cfg = self.cfg
        self._jitted = jax.jit(
            lambda p, b: G.apply_packed(p, cfg, b, None, policy))
        self._valid_log: list = []
        self.params = params
        if shards > 1:
            from repro.distributed.sharding import replicated
            from repro.launch.mesh import make_data_mesh
            mesh = make_data_mesh(shards)
            self.sharded_fn = G.make_sharded_apply(cfg, mesh, None, policy)
            self.params = jax.device_put(params, replicated(mesh))

    def fn(self, params, batch):
        self._valid_log.append(batch["graph_valid"])
        return self._jitted(params, batch)

    def drain(self, queue, idx) -> DrainRaw:
        import jax
        from repro.launch import serve
        if self.shards > 1:
            outs, stats = serve.drain_gnn_queue_sharded(
                self.sharded_fn, self.params, queue, self.node_budget,
                self.edge_budget, self.batch_graphs, self.shards, None,
                task="graph", partition_fn=None, validate=True)
            return DrainRaw(idx, outs, None, stats["outcomes"],
                            stats["n_batches"])
        start = len(self._valid_log)
        outs, stats = serve.drain_gnn_queue(
            self.fn, self.params, queue, self.node_budget, self.edge_budget,
            self.batch_graphs, None, partition_fn=None, validate=True)
        outs = jax.device_get(outs)
        return DrainRaw(idx, outs, self._valid_log[start:],
                        stats["outcomes"], stats["n_batches"])

    def scheduler(self, clock):
        import jax
        from repro.core import gnn_model as G
        from repro.runtime import scheduler as S
        fn, params = self._jitted, self.params
        executor = S.MeasuredExecutor(
            batch_fn=lambda b: np.asarray(jax.block_until_ready(
                fn(params, G.packed_to_device(b)))))
        sv = self.serving
        return S.ContinuousScheduler(
            S.SchedulerConfig(
                self.node_budget, self.edge_budget, self.batch_graphs,
                max_queue_depth=sv["queue_depth"],
                default_tier=S.SLOTier("standard",
                                       sv["deadline_ms"] / 1e3, 1),
                max_retries=sv["max_retries"], validate=True),
            executor, clock=clock)

    def close(self):
        self._jitted = self.sharded_fn = self.params = None
        self._valid_log = []


# ------------------------------------------------------- trace spans --
def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


# ------------------------------------------------------- screen driver --
def screen_warmup(sut: Sut, pool: list, traffic: dict, seed: int):
    """Drain calls for ``warmup_s`` seconds (two at the least): the
    first compiles or loads the program, the rest run the path hot."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x3A12]))
    end = time.perf_counter() + float(traffic.get("warmup_s", 0.0))
    calls = 0
    while calls < 2 or time.perf_counter() < end:
        idx = rng.integers(0, len(pool), traffic["chunk_graphs"])
        sut.drain([pool[i] for i in idx], idx)
        calls += 1
    sut._valid_log.clear()


def run_screen(sut: Sut, pool: list, traffic: dict, seed: int,
               seconds: float) -> dict:
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x5C4E]))
    calls = []
    t0 = time.perf_counter()
    end = t0 + seconds
    while True:
        idx = rng.integers(0, len(pool), traffic["chunk_graphs"])
        with span("bench.drain"):
            calls.append(sut.drain([pool[i] for i in idx], idx))
        if time.perf_counter() >= end:
            break
    window_s = time.perf_counter() - t0
    return {"mode": "screen", "calls": calls, "window_s": window_s}


def screen_answers(rec: dict) -> dict:
    """Map each drain call's batch rows back to its requests: the served
    requests in queue order take the valid rows in batch order."""
    from repro.runtime import scheduler as S
    idx_all, rows_all = [], []
    attempted = lost = unserved = launches = 0
    for c in rec["calls"]:
        attempted += len(c.idx)
        launches += c.launches
        served = [o["index"] for o in c.outcomes
                  if o["status"] == S.SERVED_PACKED]
        unserved += len(c.idx) - len(served)
        valid = c.valid if c.valid is not None else [None] * len(c.outs)
        parts = [np.asarray(o) if v is None else np.asarray(o)[np.asarray(v)]
                 for o, v in zip(c.outs, valid)]
        rows = np.concatenate(parts) if parts \
            else np.zeros((0, 1), np.float32)
        if len(rows) != len(served) or len(c.outcomes) != len(c.idx):
            lost += max(len(served), 1)     # rows cannot be attributed
            continue
        idx_all.append(np.asarray(c.idx)[served])
        rows_all.append(rows)
    return {"attempted": attempted, "served": attempted - unserved - lost,
            "unserved": unserved, "lost": lost, "launches": launches,
            "window_s": rec["window_s"],
            "idx": np.concatenate(idx_all) if idx_all
            else np.zeros((0,), np.int64),
            "rows": np.concatenate(rows_all) if rows_all
            else np.zeros((0, 1), np.float32)}


# ------------------------------------------------------- online driver --
class WallClock:
    """The scheduler's clock: seconds since the benchmark started it.
    ``advance_to`` waits for the time to come; it never runs back."""

    def __init__(self):
        self._t0 = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self._t0

    def advance_to(self, t: float):
        wait_until(self, t)


def wait_until(clock, t: float):
    wait = t - clock.now()
    if wait > 5e-4:
        time.sleep(wait - 3e-4)
    while clock.now() < t:
        pass


def online_warmup(sut: Sut, pool: list, traffic: dict, seed: int):
    """Two full batches and one part-filled batch through a scheduler of
    their own, then ``warmup_s`` seconds of the cell's own traffic on
    another, so that the window starts on a path already running at its
    rate."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x3A13]))
    sched = sut.scheduler(WallClock())
    for i in rng.integers(0, len(pool), 2 * sut.batch_graphs + 5):
        sched.submit(pool[i])
        if sched.inflight:
            sched.tick()
    sched.drain()
    if traffic.get("warmup_s"):
        run_online(sut, pool, traffic, seed ^ 0x3A14,
                   float(traffic["warmup_s"]))


def run_online(sut: Sut, pool: list, traffic: dict, seed: int,
               seconds: float) -> dict:
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x0A11]))
    rate = float(traffic["rate_per_s"])
    gaps = rng.exponential(1.0 / rate, int(rate * seconds * 1.3) + 64)
    due = np.cumsum(gaps)
    while due[-1] < seconds:           # never fewer arrivals than the window
        due = np.concatenate([due, due[-1] + np.cumsum(
            rng.exponential(1.0 / rate, len(due)))])
    due = due[due < seconds]
    idx = rng.integers(0, len(pool), len(due))
    clock = WallClock()
    sched = sut.scheduler(clock)
    n = len(due)
    t_start = clock.now() + 1e-3
    due = due + t_start
    rid = np.full(n, -1, np.int64)
    submit_t = np.zeros(n)
    seen = np.full(n, np.nan)       # by request id: when its answer came
    n_seen = 0

    def collect():
        nonlocal n_seen
        rs = sched.responses
        if len(rs) > n_seen:
            t = clock.now()
            for r in rs[n_seen:]:
                if 0 <= r.req_id < n and np.isnan(seen[r.req_id]):
                    seen[r.req_id] = t
            n_seen = len(rs)

    i = 0
    while i < n:
        if due[i] <= clock.now():
            with span("bench.submit"):
                rid[i] = sched.submit(pool[idx[i]])
            submit_t[i] = clock.now()
            i += 1
            if sched.inflight:
                with span("bench.tick"):
                    sched.tick()
            collect()
            continue
        with span("bench.tick"):
            sched.tick()
        collect()
        nxt = due[i]
        ev = sched.next_event_s()
        if ev is not None:
            nxt = min(nxt, ev)
        if nxt > clock.now():
            with span("bench.wait"):
                wait_until(clock, nxt)
    close_s = clock.now()
    # arrivals are over: every request still held is answered as the
    # scheduler's deadlines come due, and its latency counts that wait
    while sched.pending or sched.inflight:
        ev = sched.next_event_s()
        if ev is None:
            break
        with span("bench.wait"):
            wait_until(clock, ev)
        with span("bench.tick"):
            sched.tick()
        collect()
    return {"mode": "online", "due": due, "idx": idx, "rid": rid,
            "submit_t": submit_t, "seen": seen,
            "responses": list(sched.responses), "window_s": close_s - t_start,
            "launches": len(sched.launches)}


def online_answers(rec: dict) -> dict:
    from repro.runtime import scheduler as S
    by_rid: dict = {}
    dup = 0
    for r in rec["responses"]:
        if r.req_id in by_rid:
            dup += 1
        by_rid[r.req_id] = r
    idx_all, rows_all, lat, qwait, svc = [], [], [], [], []
    lost = unserved = 0
    for i in range(len(rec["due"])):
        r = by_rid.get(int(rec["rid"][i]))
        if r is None:
            lost += 1
            continue
        if r.status != S.SERVED_PACKED or r.output is None:
            unserved += 1
            continue
        idx_all.append(rec["idx"][i])
        rows_all.append(np.asarray(r.output).reshape(-1))
        lat.append(float(rec["seen"][r.req_id] - rec["due"][i]))
        qwait.append(r.launch_s - r.arrival_s)
        svc.append(r.complete_s - r.launch_s)
    return {"attempted": len(rec["due"]),
            "served": len(idx_all), "unserved": unserved, "lost": lost + dup,
            "launches": rec["launches"], "window_s": rec["window_s"],
            "idx": np.asarray(idx_all, np.int64),
            "rows": np.stack(rows_all) if rows_all
            else np.zeros((0, 1), np.float32),
            "latency_s": lat, "queue_wait_s": qwait, "service_s": svc,
            "gen_lag_s": list(rec["submit_t"] - rec["due"])}


# ------------------------------------------------------------- check --
def reference_for(config: dict, seed: int, pool_mols: list, idx,
                  precision: str = "highest") -> dict:
    """Reference output of every distinct pool molecule in ``idx``."""
    uniq = np.unique(np.asarray(idx, np.int64))
    if not len(uniq):
        return {}
    params = make_weights(config["model"], seed)
    ref = config["reference"]
    out = bench_reference.reference_outputs(
        params, config["model"], [pool_mols[i] for i in uniq],
        node_pad=ref["node_pad"], edge_pad=ref["edge_pad"],
        block_graphs=ref["block_graphs"], precision=precision)
    return dict(zip(uniq.tolist(), out))


def compare(ans: dict, ref: dict, limits: dict) -> dict:
    """The numbers that decide ``correct``, each with its limit.

    ``max_err``: the widest gap between a served row and the reference
    row of its molecule, over every served request, as a share of the
    root mean square of the reference outputs of the molecules served.
    ``rms_err``: the root mean square of those gaps, on the same scale.
    ``lost``: requests the window made that got no answer, or more than
    one. ``unserved``: requests refused or failed. Only the numbers that
    ``limits`` names are compared."""
    rows = np.asarray(ans["rows"], np.float64)
    max_err = rms_err = float("inf")
    if len(rows):
        want = np.stack([ref[int(i)] for i in ans["idx"]]).astype(np.float64)
        scale = max(float(np.sqrt(np.mean(np.square(
            np.stack(list(ref.values())).astype(np.float64))))), 1e-30)
        gap = np.abs(rows - want)
        if np.isfinite(gap).all():
            max_err = float(np.max(gap)) / scale
            rms_err = float(np.sqrt(np.mean(gap * gap))) / scale
    readings = {"max_err": max_err, "rms_err": rms_err,
                "lost": ans["lost"], "unserved": ans["unserved"]}
    return {k: {"value": readings[k], "limit": limits[k]}
            for k in readings if k in limits}


def passes(check: dict) -> bool:
    return all(v["value"] <= v["limit"] for v in check.values())


# ------------------------------------------------------------ metrics --
def end_to_end(cell: Cell, ans: dict, setup_s: float):
    out = {}
    for m in cell.end_to_end:
        name = m["name"]
        if name == "setup_s":
            value = setup_s
        elif name == "graphs_per_s":
            value = ans["served"] / ans["window_s"]
        elif re.fullmatch(r"p\d\d_ms", name):
            q = bench_stats.percentile(ans["latency_s"], int(name[1:3]))
            value = None if q is None else q * 1e3
        else:
            raise ValueError(f"no reading for end-to-end metric {name!r}")
        out[name] = {"value": value, "unit": m["unit"]}
    return out


def load_reader(name: str):
    """The per-layer metric's reader, ``metrics/<name>.py``."""
    import importlib.util
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class RunView:
    """What a per-layer reader may read."""
    cell: Cell
    ans: dict
    trace: dict | None
    flops: float           # model FLOPs of the graphs served in the window
    peak: dict
    chips: int


def per_layer(view: RunView) -> dict:
    out = {}
    for m in view.cell.per_layer:
        value = load_reader(m["name"])(view)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def served_flops(model: dict, pool_mols: list, idx) -> float:
    per = {}
    total = 0
    for i in np.asarray(idx, np.int64).tolist():
        if i not in per:
            m = pool_mols[i]
            per[i] = bench_flops.graph_flops(model, m["num_nodes"],
                                             m["num_edges"])
        total += per[i]
    return float(total)


def free_device_state(*objs):
    for o in objs:
        if o is not None and hasattr(o, "close"):
            o.close()
    gc.collect()
