"""The fused encoder kernel (projection, sign, pack) against its roofline."""
from bench import layers, work


def read(ctx):
    b, f, d, _ = layers.serve_batch(ctx)
    return layers.roofline_pct(ctx, "encode_pack", work.encode_pack(b, f, d))
