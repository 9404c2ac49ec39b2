"""core/memhd fit: host milliseconds an epoch outside the host sync, the
``fit`` spans less their ``fit.sync`` children, over their ``fit.epoch``
children."""
from bench import spans


def read(ctx):
    return spans.self_ms_per_child(spans.events(), "fit", "fit.sync",
                                   "fit.epoch")
