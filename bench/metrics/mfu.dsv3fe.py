"""Whole serving step's share of the bf16 peak: rows answered per second
in the traced window times the operations of one row
(``work_dsv3.row_flops``: dense layers, causal attention, the shared
expert, the held experts at the routed slots the program counted, the
encode and the search), over the peak."""
from bench import work_dsv3


def read(ctx):
    from repro.obs import metrics
    run = ctx["run"]
    slots = metrics.REGISTRY.get("moe_local_slots_total")
    tokens = metrics.REGISTRY.get("frontend_tokens_total")
    if (run.trace_summary is None or "traced" not in run.counts
            or slots is None or tokens is None or not tokens.total()):
        return None
    cfg, t = ctx["cfg"], run.traffic
    moe_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    per_token = slots.total() / tokens.total() / moe_layers
    rate = run.counts["traced"]["rows"] / run.trace_summary.window_s
    return 100.0 * rate * work_dsv3.row_flops(
        cfg, t["row_tokens"], per_token) / ctx["peak"]["bf16_flops"]
