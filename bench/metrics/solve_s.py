"""Wall seconds per all-pairs solve: the window's elapsed time over the
count of whole solves in it (no solve dropped or pro-rated)."""
from bench import readers


def read(ctx):
    return readers.per_step_s(ctx)
