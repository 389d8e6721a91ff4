"""Packing time per launch of the continuous scheduler, in
milliseconds: the program span ``pack.graphs`` (``pack_graphs`` in
``ContinuousScheduler._launch``) over its count. It falls between the
launch time and the service time, so neither ``queue_wait_ms.online``
nor ``service_ms.online`` holds it."""

import bench_spans


def read(view):
    s = bench_spans.span("pack.graphs")
    if s is None or not s["n"]:
        return None
    return 1e3 * s["total_s"] / s["n"]
