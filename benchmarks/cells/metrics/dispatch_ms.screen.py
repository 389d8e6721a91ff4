"""Dispatch time per launch of the screen drain, in milliseconds: the
self time of the program span ``device.launch`` (the call of the jitted
program, which returns once the work is enqueued; the transfer inside
it is ``device.put``'s) over the count of those spans."""

import bench_spans


def read(view):
    return bench_spans.per_launch_ms("device.launch")
