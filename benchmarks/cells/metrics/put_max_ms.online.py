"""The longest host-to-device transfer of one online launch, in
milliseconds: the longest program span ``device.put``
(``packed_to_device`` inside ``exec.run_batch``). Beside
``service_max_ms.online`` it says whether a launch stall sits in the
transfer."""

import bench_spans


def read(view):
    s = bench_spans.span("device.put")
    return None if s is None else 1e3 * s["max_s"]
