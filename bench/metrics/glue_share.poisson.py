"""Share (%) of device busy time spent in operations other than the named
Pallas kernels: probe lists, LUT build, gathers, merges, pads."""
from bench import readers


def read(ctx):
    return readers.glue_share(ctx)
