"""Mean length of the pending list the scheduler sorts per selection,
in requests: the program counter ``sched.scanned`` over
``sched.selects`` (each ``_ordered_pending`` call adds one select and
the number of ready requests it sorted)."""

import bench_spans


def read(view):
    selects = bench_spans.counter("sched.selects")
    scanned = bench_spans.counter("sched.scanned")
    if not selects or scanned is None:
        return None
    return scanned / selects
