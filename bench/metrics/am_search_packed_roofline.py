"""The flat packed search kernel against its roofline."""
from bench import layers, work


def read(ctx):
    b, _, d, c = layers.serve_batch(ctx)
    return layers.roofline_pct(ctx, "am_search_packed",
                               work.search_flat(b, d, c))
