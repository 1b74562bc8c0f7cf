"""Share (%) of the query rows sent to the index that were pow2 padding:
the window's flush sizes set against the engine's buckets."""
from bench import traffic


def read(ctx):
    pad, sent = traffic.pad_rows(ctx.mix, ctx.out.get("flush_sizes", []))
    return 100.0 * pad / sent if sent else None
