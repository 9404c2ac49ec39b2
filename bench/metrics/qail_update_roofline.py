"""The fused QAIL minibatch kernel against its roofline."""
from bench import layers, work


def read(ctx):
    c = ctx["cfg"]
    return layers.roofline_pct(ctx, "qail_update", work.qail_step(
        c["qail"]["batch_size"], c["dim"], c["columns"]))
