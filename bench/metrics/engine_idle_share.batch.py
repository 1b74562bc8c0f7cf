"""Share (%) of the traced window in which the device ran nothing while
the engine's innermost open host span was a ``knn.*`` span (flush, chunk,
pad, state, dispatch, wait, fetch): idle time the serving path itself
holds, mean over devices.  Logs the idle seconds by span."""
from bench import spans


def read(ctx):
    return spans.engine_idle_share(spans.of(ctx))
