"""99th percentile of every request of the open-loop window, each timed
from its due time to the moment its answer is on the host."""
from bench import readers


def read(ctx):
    return readers.percentile_ms(ctx, 99)
