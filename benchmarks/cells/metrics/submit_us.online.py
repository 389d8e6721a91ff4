"""Scheduler time per admitted request, in microseconds: the self time
of the program span ``sched.submit`` (``ContinuousScheduler.submit``:
validation, the queue, the launch check that re-sorts the pending list)
over its count. A launch that a submit fires is in its own spans and
not counted."""

import bench_spans


def read(view):
    s = bench_spans.span("sched.submit")
    if s is None or not s["n"]:
        return None
    return 1e6 * s["self_s"] / s["n"]
