"""Readings of the program's own spans and counters
(``repro.runtime.trace``) for the per-layer metrics.

The program records them only while a profiler session runs, and
``run.py --trace 1`` runs one over the measured window alone, so the
aggregates cover that window. A span's ``self_s`` is its time less the
time of the program spans nested in it. Every reading is None where the
program has no such module (an older checkout) or recorded nothing under
the name.
"""
from __future__ import annotations


def snapshot() -> dict | None:
    try:
        from repro.runtime import trace
    except ImportError:
        return None
    return trace.snapshot()


def span(name: str) -> dict | None:
    snap = snapshot()
    return None if snap is None else snap["spans"].get(name)


def counter(name: str):
    snap = snapshot()
    return None if snap is None else snap["counters"].get(name)


def per_launch_ms(name: str, field: str = "self_s") -> float | None:
    """``field`` of span ``name`` over the count of ``device.launch``
    spans (one per batch, or per wave of shards), in milliseconds."""
    s, d = span(name), span("device.launch")
    if s is None or d is None or not d["n"]:
        return None
    return 1e3 * s[field] / d["n"]
