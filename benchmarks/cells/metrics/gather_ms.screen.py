"""Time the sharded drain takes to bring each wave's outputs to the
host and put them in request order, per wave, in milliseconds: the
program span ``pack.gather_shards`` over the count of ``device.launch``
spans (one per wave)."""

import bench_spans


def read(view):
    return bench_spans.per_launch_ms("pack.gather_shards", field="total_s")
