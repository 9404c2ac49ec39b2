"""The coarse shortlist kernel (top S of G super-centroids) against its
roofline."""
from bench import layers, work


def read(ctx):
    b, _, d, _ = layers.serve_batch(ctx)
    dep = ctx["cfg"]["deploy"]
    return layers.roofline_pct(ctx, "am_shortlist", work.shortlist(
        b, d, dep["groups"], dep["shortlist"]))
