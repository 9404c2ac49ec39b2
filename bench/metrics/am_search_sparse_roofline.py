"""The sparse re-rank kernel (the shortlisted groups' members) against its
roofline. The kernel is the custom-call of ``am_search_sparse_gathered``,
which reads the shortlisted tiles from the slab itself, so its time holds
the tile reads as well as the popcount search."""
from bench import layers, work


def read(ctx):
    b, _, d, c = layers.serve_batch(ctx)
    dep = ctx["cfg"]["deploy"]
    return layers.roofline_pct(ctx, "am_search_sparse_gathered", work.rerank(
        b, d, c, dep["groups"], dep["shortlist"]))
