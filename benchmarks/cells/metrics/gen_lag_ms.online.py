"""How late the load generator submitted, in milliseconds: the 99th
percentile (nearest rank) of submit time minus due time over the
window's arrivals. It shares its thread with the scheduler."""

import bench_stats


def read(view):
    lag = view.ans.get("gen_lag_s")
    if not lag:
        return None
    return 1e3 * bench_stats.percentile(lag, 99)
