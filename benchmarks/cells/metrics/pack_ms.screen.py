"""Packing time per launch of the screen drain, in milliseconds: the
self time of the program span ``pack.dataset`` (``pack_dataset`` on the
admitted requests of a drain call) over the count of ``device.launch``
spans."""

import bench_spans


def read(view):
    return bench_spans.per_launch_ms("pack.dataset")
