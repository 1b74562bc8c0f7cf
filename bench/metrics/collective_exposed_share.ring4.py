"""Share (%) of the traced window in which a collective ran on a chip and
no other operation did, the mean over the chips."""
from bench import trace as tr


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    return 100.0 * tr.collective_exposed_s(ctx.trace) / ctx.trace.window_s
