"""``rescore_topk``'s share (%) of its roofline: the least time of the
window's exact rescores (bench/work.py ``rescore``) over the kernel's
device time."""
from bench import readers


def read(ctx):
    return readers.roofline_share(ctx, "rescore_topk", "rescore_topk")
