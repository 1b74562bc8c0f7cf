"""Seconds from process start to the first timed operation: data made from
the seed, the system built, every shape of the window warmed up."""


def read(ctx):
    return ctx.setup_s
