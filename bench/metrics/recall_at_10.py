"""Mean recall@10 of every query answered in the window against the
benchmark's float32 brute force (computed after the window)."""


def read(ctx):
    return ctx.readings.get("recall")
