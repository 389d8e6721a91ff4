"""Reduction of a profiler trace to the benchmark's device numbers.

Input is a flat list of events ``(plane, line, name, start_ns, dur_ns)``
as ``load_events`` reads them from the ``.xplane.pb`` file that
``jax.profiler`` writes, or as a test builds them. Planes named
``/device:TPU:<k>`` are devices; their ``XLA Ops`` line holds one event
per operation that ran. The benchmark marks its measured window with a
host span ``bench.window`` and the host's work inside it with spans
``bench.<what>``.

* busy: the union of the op intervals of one device, clipped to the
  window; ``busy_s`` is its mean over the devices.
* idle gaps: the holes in the union of device 0's busy intervals within
  the window, each named by the ``bench.*`` span that overlaps it most
  (``host.other`` where none does).
* device ops: total op time by name, summed over devices and divided by
  their number.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

WINDOW_SPAN = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def load_events(trace_dir: str) -> list:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    out = []
    for plane in data.planes:
        keep_all = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if not keep_all and not name.startswith("bench."):
                    continue
                out.append((plane.name, line.name, name,
                            float(ev.start_ns), float(ev.duration_ns)))
    return out


_OPCODE = re.compile(r"[}\)]\s([A-Za-z][\w\-]*)\(")


def op_label(name: str) -> str:
    """An op event's HLO text shortened to its name, opcode and result
    shape: ``%fusion.10 = f32[33,64]{1,0} fusion(...), kind=kLoop`` ->
    ``%fusion.10 fusion f32[33,64]``."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    m = _OPCODE.search(rest)
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    return " ".join([head] + ([m.group(1)] if m else []) + [shape])


def _union(intervals) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce_events(events: list, *, top: int = 10) -> dict | None:
    """Window, busy time, top ops and named idle gaps; None when the
    trace holds no window span or no device op inside it."""
    windows = [(s, s + d) for plane, line, name, s, d in events
               if name == WINDOW_SPAN]
    if not windows:
        return None
    lo, hi = windows[0]
    ops: dict = {}
    for plane, line, name, s, d in events:
        if plane.startswith(DEVICE_PREFIX) and line == OPS_LINE:
            ops.setdefault(plane, []).append((op_label(name), s, s + d))
    if not ops:
        return None
    devices = sorted(ops, key=lambda p: int(p[len(DEVICE_PREFIX):]
                                            .split()[0] or 0))
    busy, by_name = [], {}
    unions = {}
    for dev in devices:
        iv = _clip([(s, e) for _, s, e in ops[dev]], lo, hi)
        u = _union(iv)
        unions[dev] = u
        busy.append(sum(e - s for s, e in u))
        for name, s, e in ops[dev]:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                by_name[name] = by_name.get(name, 0.0) + (e - s)
    if sum(busy) <= 0:
        return None
    n_dev = len(devices)
    gaps = []
    prev = lo
    for s, e in unions[devices[0]] + [[hi, hi]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = sorted((s, s + d, name) for plane, line, name, s, d in events
                   if name.startswith("bench.") and name != WINDOW_SPAN)
    starts = [s for s, _, _ in spans]
    longest = max((e - s for s, e, _ in spans), default=0.0)
    named = []
    for gs, ge in gaps[:top]:
        best, best_ov = "host.other", 0.0
        for s, e, name in spans[bisect.bisect_left(starts, gs - longest):
                                bisect.bisect_right(starts, ge)]:
            ov = min(e, ge) - max(s, gs)
            if ov > best_ov:
                best, best_ov = name, ov
        named.append([best, (ge - gs) * 1e-9])
    top_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / n_dev * 1e-9,
        "busy_s_per_device": [b * 1e-9 for b in busy],
        "device_ops": [[k, v / n_dev * 1e-9] for k, v in top_ops],
        "idle_gaps": named,
    }
