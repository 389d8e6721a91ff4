"""Time the screen drain waits for the device per launch, in
milliseconds: the program span ``device.wait`` (``block_until_ready``
on a drain call's outputs) over the count of ``device.launch`` spans.
About 0 while the host sets the pace; it grows once the device does."""

import bench_spans


def read(view):
    return bench_spans.per_launch_ms("device.wait", field="total_s")
