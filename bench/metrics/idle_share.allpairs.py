"""Share (%) of the traced window in which the device ran nothing, the mean
over the cell's chips."""
from bench import readers


def read(ctx):
    return readers.idle_share(ctx)
