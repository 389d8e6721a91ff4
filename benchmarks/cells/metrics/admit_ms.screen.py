"""Admission time per launch of the screen drain, in milliseconds: the
self time of the program span ``serve.admit`` (``_admit``, which runs
``validate_graph`` on every request of a drain call) over the count of
``device.launch`` spans (one per batch, or per wave of shards)."""

import bench_spans


def read(view):
    return bench_spans.per_launch_ms("serve.admit")
