"""Median host-clock milliseconds of one blocking engine ``flush()``."""
import numpy as np


def read(ctx):
    f = ctx.out.get("flush_s")
    return float(np.median(f)) * 1e3 if f else None
