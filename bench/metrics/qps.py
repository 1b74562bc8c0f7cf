"""Queries answered over the window's elapsed time, all of them over all of
the time."""


def read(ctx):
    t = ctx.out["elapsed_s"]
    return len(ctx.out["answers"]) / t if t > 0 else None
