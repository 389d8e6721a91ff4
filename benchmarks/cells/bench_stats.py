"""Order statistics of the benchmark.

``percentile`` is a copy of ``repro.runtime.scheduler.percentile``
(nearest rank), kept here so that the yardstick does not move with the
program. ``spread`` is the run-to-run spread the bounds are set from.
"""
from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float | None:
    """Nearest-rank percentile: the smallest sample whose empirical CDF
    reaches q/100 (``sorted(values)[ceil(q/100 * n) - 1]``); None when
    there are no samples."""
    s = sorted(values)
    if not s:
        return None
    k = max(1, math.ceil(q / 100.0 * len(s)))
    return float(s[min(k, len(s)) - 1])


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of ``statistics.quantiles(values, n=4)``."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
