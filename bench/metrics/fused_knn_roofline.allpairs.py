"""``fused_knn``'s share (%) of its roofline: the least time of the
window's solves (each unordered pair scored once, bench/work.py
``allpairs``) over the kernel's device time summed over the chips."""
from bench import readers


def read(ctx):
    return readers.roofline_share(ctx, "fused_knn", "solve")
