"""Host-to-device transfer time per launch of the screen drain, in
milliseconds: the self time of the program span ``device.put``
(``packed_to_device``, or ``stack_shards`` for a wave of shards) over
the count of ``device.launch`` spans."""

import bench_spans


def read(view):
    return bench_spans.per_launch_ms("device.put")
