"""Program spans and counters of the serving path, on the profiler's clock.

Spans and counters record only while a profiler session collects host
events (``jax.profiler.trace`` or ``start_trace``); at any other time
``span`` hands back one shared no-op context manager after a single
``TraceAnnotation.is_enabled()`` check, and ``count`` does nothing. There
is no flag: an operator records by starting the profiler.

While on, each span

* opens a ``jax.profiler.TraceAnnotation`` of the same name, so it lands
  in the profiler's host plane on the same clock as the device's ops,
  with its ids (a request id, a launch ``seq``) as the event's stats;
* adds to an in-memory aggregate per name: count, total time, self time
  (its duration less the time its child spans cover) and longest time.
  The open spans of a thread form a stack that gives each span its
  parent.

``snapshot()`` returns the aggregates and counters; ``reset()`` clears
them. They outlive the profiler session, so a caller that starts the
profiler, runs a window and stops it reads that window's aggregates.

Rules for callers: no span or counter inside a loop over graphs or rows,
and no span name starting with ``bench.`` (the chip benchmark's own
spans use that prefix).
"""
from __future__ import annotations

import threading
import time

from jax.profiler import TraceAnnotation

_on = TraceAnnotation.is_enabled


def enabled() -> bool:
    """Whether spans and counters record now: for a caller whose counter
    costs work to compute, so that it skips the work outside a profiler
    session."""
    return _on()


class _NoSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NO_SPAN = _NoSpan()


class _Span:
    __slots__ = ("tracer", "name", "ann", "t0", "child_s")

    def __init__(self, tracer: "Tracer", name: str, ids: dict):
        self.tracer = tracer
        self.name = name
        self.ann = TraceAnnotation(name, **ids)
        self.child_s = 0.0

    def __enter__(self):
        self.ann.__enter__()
        self.tracer._stack().append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self.t0
        stack = self.tracer._stack()
        stack.pop()
        if stack:
            stack[-1].child_s += dur
        self.tracer._add(self.name, dur, dur - self.child_s)
        self.ann.__exit__(*exc)
        return False


class Tracer:
    """Aggregates of spans and counters recorded while the profiler is
    on. The module's ``span``/``count``/``snapshot``/``reset`` are those
    of one process-wide tracer."""

    def __init__(self):
        self._lock = threading.Lock()
        self._local = threading.local()
        self._spans: dict = {}       # name -> [n, total_s, self_s, max_s]
        self._counters: dict = {}

    def span(self, name: str, **ids):
        """A context manager timing ``name``; ``ids`` become the trace
        event's stats. The shared no-op when the profiler is off."""
        if not _on():
            return NO_SPAN
        return _Span(self, name, ids)

    def count(self, name: str, n: int = 1):
        """Add ``n`` to counter ``name`` while the profiler is on."""
        if _on():
            with self._lock:
                self._counters[name] = self._counters.get(name, 0) + n

    def snapshot(self) -> dict:
        with self._lock:
            return {"spans": {k: {"n": v[0], "total_s": v[1],
                                  "self_s": v[2], "max_s": v[3]}
                              for k, v in self._spans.items()},
                    "counters": dict(self._counters)}

    def reset(self):
        with self._lock:
            self._spans.clear()
            self._counters.clear()

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _add(self, name: str, dur: float, self_s: float):
        with self._lock:
            a = self._spans.get(name)
            if a is None:
                self._spans[name] = [1, dur, self_s, dur]
            else:
                a[0] += 1
                a[1] += dur
                a[2] += self_s
                if dur > a[3]:
                    a[3] = dur


TRACER = Tracer()
span = TRACER.span
count = TRACER.count
snapshot = TRACER.snapshot
reset = TRACER.reset
