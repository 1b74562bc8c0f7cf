"""What the metric files of ``bench/metrics/`` share.

A metric file defines ``read(ctx) -> float | None``; ``ctx`` holds the
cell, the configuration and mix, the peaks, ``setup_s``, the window's output
(``out``), the readings of the checks, the lower-bound work of the window
(``work``: stage name -> ``work.Work``) and, in a traced run, the reduced
trace (``trace``).  A reader that finds nothing to read returns None, and
the harness leaves the metric out; no share of a roofline reads 0 for want
of a kernel.
"""
from __future__ import annotations

import math

from bench import trace as tr
from bench import traffic, work

# Kernels by their pallas_call names (src/repro/kernels/).
KERNELS = ("fused_knn", "pq_scan", "rescore_topk", "ivf_scan", "stream_topk",
           "pairwise_distance_mxu", "pairwise_distance_vpu")


def finite(v):
    return float(v) if v is not None and math.isfinite(v) else None


def roofline_share(ctx, kernel: str, stage: str):
    """100 x the least time of the window's ``stage`` work over the device
    time of ``kernel`` (summed over devices, against one chip's peaks)."""
    if ctx.trace is None or stage not in ctx.work:
        return None
    t = tr.kernel_s(ctx.trace, kernel)
    w = ctx.work[stage]
    if t <= 0 or (w.ops <= 0 and w.bytes <= 0):
        return None
    return 100.0 * work.least_time(w, ctx.peaks) / t


def idle_share(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    return 100.0 * (1.0 - tr.mean_busy_s(ctx.trace) / ctx.trace.window_s)


def glue_share(ctx):
    """100 x the share of device busy time spent outside the named
    kernels."""
    if ctx.trace is None:
        return None
    busy = sum(tr.busy_s(ctx.trace).values())
    if busy <= 0:
        return None
    return 100.0 * (1.0 - tr.named_kernel_s(ctx.trace, KERNELS) / busy)


def per_step_s(ctx):
    steps = ctx.out.get("steps", 0)
    return ctx.out["elapsed_s"] / steps if steps else None


def percentile_ms(ctx, q: float):
    lat = ctx.out.get("latency_s")
    if lat is None or len(lat) == 0:
        return None
    return finite(traffic.percentile(lat, q) * 1e3)
