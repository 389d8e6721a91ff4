"""Fault-tolerant serving: deterministic fault-injection tests.

Everything runs on the injected ``VirtualClock`` with scripted or
seed-driven ``runtime.faults`` plans — closed-form retry/timeout/
quarantine timelines, dead-letter accounting, probe-back recovery, and
randomized exactly-once sweeps under >= 10% fault injection, all
bit-identical on every run with zero sleeps. The property test runs
twice: a seeded numpy sweep always, and a hypothesis-driven version
when hypothesis is installed (guarded import; the container image does
not ship it)."""
import dataclasses
import math

import numpy as np
import pytest

from repro.data import pipeline as P
from repro.runtime import scheduler as S
from repro.runtime.faults import FaultPlan, FaultSpec, FaultyExecutor

try:
    from hypothesis import given, settings
    from hypothesis import strategies as hst
    HAS_HYPOTHESIS = True
except ModuleNotFoundError:
    HAS_HYPOTHESIS = False

needs_hypothesis = pytest.mark.skipif(not HAS_HYPOTHESIS,
                                      reason="hypothesis not installed")

DS = P.GraphDataConfig(avg_nodes=8, avg_degree=2, node_feat_dim=5,
                       edge_feat_dim=3, max_nodes=64, max_edges=64, seed=3)


def lane(service: float = 0.2, with_outputs: bool = False):
    """A SimExecutor lane; ``with_outputs`` adds cheap zero outputs so
    corruption faults have an array to poison."""
    if not with_outputs:
        return S.SimExecutor(S.constant_service(service))
    return S.SimExecutor(
        S.constant_service(service),
        batch_fn=lambda b: np.zeros((len(b["graph_valid"]), 1),
                                    np.float32),
        fallback_fn=lambda g: np.zeros((1,), np.float32))


def sched_with(lanes, *, deadline: float = 0.0, timeout: float = math.inf,
               max_retries: int = 2, backoff: float = 0.0,
               backoff_cap: float = 0.5, quarantine_after: int = 2,
               cooldown: float = 0.3, validate: bool = False,
               clock=None) -> S.ContinuousScheduler:
    cfg = S.SchedulerConfig(
        1000, 1000, max_graphs=1,
        default_tier=S.SLOTier("standard", deadline, 1),
        launch_timeout_s=timeout, max_retries=max_retries,
        retry_backoff_s=backoff, retry_backoff_cap_s=backoff_cap,
        quarantine_after=quarantine_after, quarantine_cooldown_s=cooldown,
        quarantine_cooldown_cap_s=8 * cooldown if cooldown else 1.0,
        validate=validate)
    return S.ContinuousScheduler(cfg, lanes, clock=clock)


def faulty(inner, specs, clock=None) -> FaultyExecutor:
    return FaultyExecutor(inner, FaultPlan(specs), clock)


# ------------------------------------------------------------ fault plans --

def test_fault_spec_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultSpec("meteor", launch=0)
    with pytest.raises(ValueError, match="exactly one trigger"):
        FaultSpec("crash")
    with pytest.raises(ValueError, match="exactly one trigger"):
        FaultSpec("crash", launch=0, at_s=1.0)


def test_fault_plan_random_is_deterministic():
    rates = {"crash": 0.1, "hang": 0.05, "corrupt": 0.1}
    a = FaultPlan.random(seed=4, n_calls=200, rates=rates)
    b = FaultPlan.random(seed=4, n_calls=200, rates=rates)
    assert [(s.kind, s.launch) for s in a.specs] \
        == [(s.kind, s.launch) for s in b.specs]
    assert len(a.specs) > 0
    c = FaultPlan.random(seed=5, n_calls=200, rates=rates)
    assert [(s.kind, s.launch) for s in a.specs] \
        != [(s.kind, s.launch) for s in c.specs]
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultPlan.random(seed=0, n_calls=10, rates={"meteor": 0.5})


def test_faulty_executor_at_s_trigger_and_one_shot():
    clock = S.VirtualClock()
    ex = faulty(lane(0.1), [FaultSpec("slowdown", at_s=1.0, factor=3.0)],
                clock)
    g = P.make_graph(DS, 0)
    batch, _ = P.pack_graphs([g], 1000, 1000, 1)
    assert ex.run_batch(batch)[1] == pytest.approx(0.1)   # before at_s
    clock.advance_to(1.0)
    assert ex.run_batch(batch)[1] == pytest.approx(0.3)   # fires once
    assert ex.run_batch(batch)[1] == pytest.approx(0.1)   # consumed
    assert ex.injected == [(1, "slowdown")]
    assert ex.can_fallback == lane(0.1).can_fallback


# ------------------------------------------------- closed-form timelines --

def test_crash_retries_with_backoff_closed_form():
    """Crash at launch, retry after exactly the configured backoff on
    the (degraded but available) lane: latency = backoff + service."""
    lanes = [faulty(lane(0.2), [FaultSpec("crash", launch=0)])]
    sched = sched_with(lanes, backoff=0.1, quarantine_after=5)
    sched.submit(P.make_graph(DS, 0))
    sched.drain()
    (r,) = sched.responses
    assert r.status == S.SERVED_PACKED
    assert r.latency_s == pytest.approx(0.3)
    s = sched.summary()
    assert s["retries"] == 1 and s["failed_launches"] == 1
    assert s["lane_states"] == [S.LANE_HEALTHY]   # success cleared degraded
    (ev,) = [e for e in sched.events if e["kind"] == "launch_failed"]
    assert ev["error"] == S.FAIL_CRASH and ev["req_ids"] == [0]


def test_hang_resolved_by_timeout_closed_form():
    """A hung launch is reclaimed at exactly launch + timeout and the
    request re-packs immediately (zero backoff):
    latency = timeout + service = 0.25 + 0.2 = 0.45."""
    lanes = [faulty(lane(0.2), [FaultSpec("hang", launch=0)])]
    sched = sched_with(lanes, timeout=0.25, quarantine_after=5)
    sched.submit(P.make_graph(DS, 0))
    sched.drain()
    (r,) = sched.responses
    assert r.status == S.SERVED_PACKED
    assert r.latency_s == pytest.approx(0.45)
    assert sched.launches[0]["status"] == S.FAIL_TIMEOUT
    assert sched.launches[1]["status"] == "ok"


def test_hang_without_timeout_is_an_error_not_a_deadlock():
    lanes = [faulty(lane(0.2), [FaultSpec("hang", launch=0)])]
    sched = sched_with(lanes)         # launch_timeout_s = inf
    with pytest.raises(RuntimeError, match="launch_timeout_s"):
        sched.submit(P.make_graph(DS, 0))


def test_nonfinite_output_quarantines_and_reruns():
    """NaN-poisoned outputs fail the launch at completion; with
    quarantine_after=1 the lane quarantines and the batch re-runs on
    the healthy lane."""
    lanes = [faulty(lane(0.25, with_outputs=True),
                    [FaultSpec("corrupt", launch=0)]),
             lane(0.25, with_outputs=True)]
    sched = sched_with(lanes, quarantine_after=1)
    sched.submit(P.make_graph(DS, 0))
    sched.drain()
    (r,) = sched.responses
    assert r.status == S.SERVED_PACKED and r.executor == 1
    assert r.latency_s == pytest.approx(0.5)
    assert np.isfinite(r.output).all()
    assert sched.launches[0]["status"] == S.FAIL_NONFINITE
    assert sched.summary()["lane_states"][0] == S.LANE_QUARANTINED
    (q,) = [e for e in sched.events if e["kind"] == "quarantine"]
    assert q["executor"] == 0 and q["reason"] == S.FAIL_NONFINITE


def test_dead_letter_after_max_retries():
    """max_retries=2 means three failed launches dead-letter the request
    with the explicit ``failed`` status — never a hang, never a silent
    drop — while later requests still serve."""
    lanes = [faulty(lane(0.1), [FaultSpec("crash", launch=i)
                                for i in range(3)])]
    sched = sched_with(lanes, max_retries=2, quarantine_after=10)
    sched.submit(P.make_graph(DS, 0))
    sched.drain()
    (r,) = sched.responses
    assert r.status == S.FAILED
    s = sched.summary()
    assert s["failed"] == 1 and s["retries"] == 2
    assert s["failed_launches"] == 3
    sched.submit(P.make_graph(DS, 1))     # the lane still serves
    sched.drain()
    assert sched.responses[-1].status == S.SERVED_PACKED


class _Raising:
    """An executor lane whose launches raise, as a chip compile or
    out-of-memory error would."""
    can_fallback = False

    def __init__(self, *messages):
        self.messages = list(messages)

    def run_batch(self, batch):
        raise RuntimeError(self.messages.pop(0))


def test_first_executor_error_reaches_summary():
    """The first exception's type and message are kept in summary(),
    later ones do not overwrite it, and a fault-free run reports None."""
    sched = sched_with([_Raising("Mosaic failed to compile TPU kernel",
                                 "RESOURCE_EXHAUSTED", "third")],
                       max_retries=2, quarantine_after=10)
    sched.submit(P.make_graph(DS, 0))
    sched.drain()
    s = sched.summary()
    assert s["failed"] == 1
    assert s["first_error"] == \
        "RuntimeError: Mosaic failed to compile TPU kernel"
    ok = sched_with([lane(0.1)])
    ok.submit(P.make_graph(DS, 0))
    ok.drain()
    assert ok.summary()["first_error"] is None


def test_serve_exits_nonzero_and_prints_first_error(monkeypatch):
    """``serve --gnn`` fails the process when any request ends
    ``failed``, with the first executor error in the exit message."""
    from repro.launch import serve

    class Crashing(S.MeasuredExecutor):
        def run_batch(self, batch):
            raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(S, "MeasuredExecutor", Crashing)
    monkeypatch.setattr(serve.compile_cache, "enable", lambda: None)
    with pytest.raises(SystemExit) as exc:
        serve.main(["--gnn", "--reduced", "--requests", "4",
                    "--batch-graphs", "4", "--scheduler", "continuous",
                    "--max-retries", "1"])
    msg = str(exc.value.code)
    assert "4 requests failed" in msg
    assert "RuntimeError: Mosaic failed to compile TPU kernel" in msg


def test_quarantine_and_probe_back_closed_form():
    """Two consecutive crashes quarantine lane 1 with probe_at exactly
    failure time + cooldown; once eligible and the healthy lane is
    busy, the next launch is the canary probe, and success returns the
    lane to the pool (with an elastic pool replan on each transition)."""
    lanes = [lane(0.2),
             faulty(lane(0.2), [FaultSpec("crash", launch=0),
                                FaultSpec("crash", launch=1)])]
    sched = sched_with(lanes, cooldown=0.3)
    sched.submit(P.make_graph(DS, 0))     # lane 0 busy
    sched.submit(P.make_graph(DS, 1))     # lane 1: crash, crash -> quarantine
    sched.drain()
    (q,) = [e for e in sched.events if e["kind"] == "quarantine"]
    assert q["executor"] == 1 and q["probe_at_s"] == pytest.approx(0.3)
    assert sched.summary()["quarantined_executors"] == [1]
    # req 1 re-packed onto the healthy lane after its 0.2 s launch
    r1 = next(r for r in sched.responses if r.req_id == 1)
    assert r1.status == S.SERVED_PACKED and r1.executor == 0
    assert r1.latency_s == pytest.approx(0.4)
    # past probe_at with lane 0 busy: the next launch is the canary
    sched.clock.advance_to(0.5)
    sched.submit(P.make_graph(DS, 2))     # lane 0
    sched.submit(P.make_graph(DS, 3))     # lane 1 probe
    sched.drain()
    probe = next(l for l in sched.launches if l["probe"])
    assert probe["executor"] == 1 and probe["status"] == "ok"
    s = sched.summary()
    assert s["probes"] == {"succeeded": 1, "failed": 0}
    assert s["lane_states"] == [S.LANE_HEALTHY, S.LANE_HEALTHY]
    assert any(e["kind"] == "probe_success" for e in sched.events)
    # pool replans rode every transition: 2 lanes -> 1 -> 2
    assert [p["n_lanes"] for p in sched.pool_events] == [2, 1, 2]


def test_last_lane_quarantine_recovers_via_probe():
    """Hard failures may quarantine the last lane; the probe-back bounds
    the outage instead of deadlocking the drain."""
    lanes = [faulty(lane(0.2), [FaultSpec("crash", launch=0),
                                FaultSpec("crash", launch=1)])]
    sched = sched_with(lanes, max_retries=5, cooldown=0.1)
    sched.submit(P.make_graph(DS, 0))
    sched.drain()                         # must terminate
    (r,) = sched.responses
    assert r.status == S.SERVED_PACKED
    # crash at t=0 twice, probe eligible at 0.1, served at 0.1 + 0.2
    assert r.latency_s == pytest.approx(0.3)
    s = sched.summary()
    assert s["probes"]["succeeded"] == 1
    assert s["lane_states"] == [S.LANE_HEALTHY]


def test_probe_failure_requarantines_with_doubled_cooldown():
    lanes = [lane(0.2),
             faulty(lane(0.2), [FaultSpec("crash", launch=i)
                                for i in range(3)])]
    sched = sched_with(lanes, cooldown=0.3, max_retries=5)
    sched.submit(P.make_graph(DS, 0))
    sched.submit(P.make_graph(DS, 1))
    sched.drain()
    sched.clock.advance_to(0.5)
    sched.submit(P.make_graph(DS, 2))     # lane 0 busy
    sched.submit(P.make_graph(DS, 3))     # lane 1 probe -> crash
    sched.drain()
    s = sched.summary()
    assert s["probes"]["failed"] == 1
    assert s["quarantined_executors"] == [1]
    q = [e for e in sched.events if e["kind"] == "quarantine"]
    assert q[-1]["reason"] == f"probe_failed:{S.FAIL_CRASH}"
    # second quarantine doubles the cooldown: probe_at = 0.5 + 0.6
    assert q[-1]["probe_at_s"] == pytest.approx(1.1)
    # the probed request still resolved on the healthy lane
    r3 = next(r for r in sched.responses if r.req_id == 3)
    assert r3.status == S.SERVED_PACKED and r3.executor == 0


def test_validate_rejects_malformed_graph_at_admission():
    g = P.make_graph(DS, 0)
    nf = np.array(g.node_feat, copy=True)
    nf[0, 0] = np.nan
    bad = dataclasses.replace(g, node_feat=nf)
    sched = sched_with([lane(0.1)], validate=True)
    sched.submit(bad)
    sched.submit(P.make_graph(DS, 1))
    sched.drain()
    by_id = {r.req_id: r for r in sched.responses}
    assert by_id[0].status == S.REJECTED_INVALID
    assert by_id[1].status == S.SERVED_PACKED
    (ev,) = [e for e in sched.events if e["kind"] == "rejected_invalid"]
    assert "non-finite node features" in ev["reason"]
    assert sched.summary()["rejected_invalid"] == 1


# ------------------------------------------------- exactly-once property --

def _chaos_exactly_once_body(seed: int, n: int, load: float,
                             fault_scale: float):
    """Under seed-driven crash+hang+corrupt+slowdown injection (>= 10%
    of launches at fault_scale >= 1) plus malformed and oversize
    arrivals, every submitted request resolves to exactly one terminal
    status — none lost, none duplicated — and quarantined lanes never
    deadlock the drain."""
    rates = {k: v * fault_scale for k, v in
             {"crash": 0.06, "hang": 0.04, "corrupt": 0.06,
              "slowdown": 0.04}.items()}
    cfg = S.SchedulerConfig(
        64, 1000, max_graphs=4, max_queue_depth=64,
        default_tier=S.SLOTier("standard", 0.02, 1),
        launch_timeout_s=0.05, max_retries=2, retry_backoff_s=0.005,
        retry_backoff_cap_s=0.04, quarantine_after=2,
        quarantine_cooldown_s=0.05, quarantine_cooldown_cap_s=0.4,
        validate=True)
    clock = S.VirtualClock()
    lanes = [FaultyExecutor(
        S.SimExecutor(S.constant_service(0.01),
                      batch_fn=lambda b: np.zeros(
                          (len(b["graph_valid"]), 1), np.float32),
                      fallback_fn=lambda g: np.zeros((1,), np.float32)),
        FaultPlan.random(seed=seed * 3 + i, n_calls=4 * n, rates=rates),
        clock) for i in range(3)]
    sched = S.ContinuousScheduler(cfg, lanes, clock=clock)
    trace = S.poisson_trace(n, load, DS, seed=seed)

    def mangle(i, g):
        if i % 11 == 5:       # oversize: rides the fallback lanes
            return dataclasses.replace(g, num_nodes=70)
        if i % 13 == 7:       # malformed: rejected at admission
            nf = np.array(g.node_feat, copy=True)
            nf[0, 0] = np.inf
            return dataclasses.replace(g, node_feat=nf)
        return g
    trace = [(t, mangle(i, g), tn) for i, (t, g, tn) in enumerate(trace)]
    S.run_trace(sched, trace)
    assert sorted(r.req_id for r in sched.responses) == list(range(n))
    s = sched.summary()
    terminal = (s["served"] + s["rejected_queue_full"]
                + s["rejected_oversize"] + s["rejected_invalid"]
                + s["failed"])
    assert terminal == n
    for i in range(n):
        if i % 11 != 5 and i % 13 == 7:
            r = next(r for r in sched.responses if r.req_id == i)
            assert r.status == S.REJECTED_INVALID
    return s


def test_chaos_exactly_once_randomized_sweep():
    rng = np.random.default_rng(1)
    doses = []
    for seed in range(10):
        s = _chaos_exactly_once_body(
            seed, n=int(rng.integers(20, 80)),
            load=float(rng.uniform(50, 600)),
            fault_scale=float(rng.uniform(0.5, 2.5)))
        doses.append(s["failed_launches"])
    assert sum(doses) > 0, "the sweep never actually injected a failure"


if HAS_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(seed=hst.integers(0, 2**16), n=hst.integers(5, 60),
           load=hst.floats(20.0, 600.0),
           fault_scale=hst.floats(0.25, 2.5))
    def test_chaos_exactly_once_hypothesis(seed, n, load, fault_scale):
        _chaos_exactly_once_body(seed, n, load, fault_scale)
else:
    @needs_hypothesis
    def test_chaos_exactly_once_hypothesis():
        pass  # covered by test_chaos_exactly_once_randomized_sweep above
