"""``pq_scan``'s share (%) of its roofline: the least time of the window's
ADC scans (bench/work.py ``pq_scan``) over the kernel's device time."""
from bench import readers


def read(ctx):
    return readers.roofline_share(ctx, "pq_scan", "pq_scan")
