"""Model FLOP utilization of the step, in percent: the benchmark's own
FLOP count of the graphs served in the traced window (``bench_flops``,
real node and edge counts, no padding) over the window, the cell's
chips and the chip's bf16 peak (``peaks.json``)."""


def read(view):
    t = view.trace
    if t is None or t["window_s"] <= 0 or not view.flops:
        return None
    peak = view.peak["bf16_flops_per_s"]
    return 100.0 * view.flops / (t["window_s"] * view.chips * peak)
