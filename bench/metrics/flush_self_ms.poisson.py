"""Median over the window's flushes of the ``knn.flush`` span minus the
time its ``knn.wait`` spans cover (ms): the engine's host cost per flush."""
from bench import spans


def read(ctx):
    return spans.flush_self_ms(spans.of(ctx))
