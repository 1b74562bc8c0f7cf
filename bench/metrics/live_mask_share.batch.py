"""Share (%) of device busy time in ops under the ``knn.live_mask`` scope:
the packed live mask and filter bitmaps built per search.  Logs device
seconds by stage."""
from bench import spans


def read(ctx):
    return spans.live_mask_share(spans.of(ctx))
