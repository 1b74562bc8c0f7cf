"""The whole solve's share (%) of the bf16 peak while the device works:
the window's needed operations (bench/work.py ``allpairs``) over the device
busy time summed over the chips, times one chip's peak.  It reads every
operation a solve runs, so it bounds the solve whichever kernels do the
work."""
from bench import trace as tr


def read(ctx):
    w = ctx.work.get("solve")
    if ctx.trace is None or w is None:
        return None
    busy = sum(tr.busy_s(ctx.trace).values())
    if busy <= 0:
        return None
    return 100.0 * w.ops / (busy * ctx.peaks["bf16_flops_per_s"])
