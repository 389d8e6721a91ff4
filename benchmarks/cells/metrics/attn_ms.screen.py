"""Device time of the segment-masked attention kernel per launch, in
milliseconds: the time of the device ops of the Pallas call named
``segment_attention`` (one per GPS layer, op labels
``%segment_attention.<n> custom-call ...``) in the traced window's top
ops, over the count of ``device.launch`` spans. None where the trace
holds no such op (a program without the kernel)."""

import bench_spans

KERNEL = "segment_attention"


def kernel_s(view):
    """Seconds of the kernel's ops in the window, or None."""
    t = view.trace
    if t is None:
        return None
    ops = [s for label, s in t["device_ops"]
           if label.lstrip("%").startswith(KERNEL)]
    return sum(ops) if ops else None


def read(view):
    s, d = kernel_s(view), bench_spans.span("device.launch")
    if s is None or d is None or not d["n"]:
        return None
    return 1e3 * s / d["n"]
