"""Whole training step's share of the bf16 peak: QAIL samples per second
in the traced window times the operations of one sample-epoch, over the peak."""
from bench import work


def read(ctx):
    run = ctx["run"]
    rate = run.counts["traced"]["samples"] / run.trace_summary.window_s
    return 100.0 * rate * work.train_sample(ctx["cfg"]) / ctx["peak"][
        "bf16_flops"]
