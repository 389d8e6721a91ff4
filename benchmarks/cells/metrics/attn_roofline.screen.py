"""Share of its roofline that the segment-masked attention kernel
reaches, in percent: ``max(F / peak, B / bw) / t``, with ``F`` and
``B`` the FLOPs and bytes of the real attention work of the window
(the family's ``attention_work`` over the program counters
``attn.pairs`` and ``attn.rows``: QK^T and PV over the real query-key
pairs, q, k, v and the output once at fp32, every layer), ``t`` the
kernel's device time in the window (``attn_ms.screen``'s ops), ``peak``
and ``bw`` the chip's bf16 peak and HBM bandwidth (``peaks.json``). It
counts the same work whatever implements it."""

import importlib.util
from pathlib import Path

import bench_spans


def _attn_ms():
    path = Path(__file__).with_name("attn_ms.screen.py")
    spec = importlib.util.spec_from_file_location("bench_metric_attn_ms",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(view):
    work = getattr(view.cell.family, "attention_work", None)
    pairs = bench_spans.counter("attn.pairs")
    rows = bench_spans.counter("attn.rows")
    t = _attn_ms().kernel_s(view)
    if work is None or not pairs or not rows or not t or not view.peak:
        return None
    flops, nbytes = work(view.cell.config["model"], pairs, rows)
    bound = max(flops / view.peak["bf16_flops_per_s"],
                nbytes / view.peak["hbm_bytes_per_s"])
    return 100.0 * bound / t
