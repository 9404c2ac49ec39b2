"""Helpers the per-layer readers in ``bench/metrics`` share.

A reader is ``read(ctx) -> float | None`` with ``ctx`` holding ``run``
(``bench.harness.Run``: the window's spans, counts and trace summary),
``cell``, ``cfg``, ``end_to_end`` and ``peak`` (``bench.work.peaks``).
It returns None where it finds nothing to read.
"""
from bench import work


def host_ms_per_batch(ctx):
    """Mean host milliseconds per batch in the program's ``host_prep``
    and ``dispatch`` spans (``repro.obs.span``) recorded in the window."""
    spans = ctx["run"].spans
    prep = [d for n, d in spans if n == "host_prep"]
    disp = [d for n, d in spans if n == "dispatch"]
    if not disp:
        return None
    n = min(len(prep), len(disp))
    return (sum(prep[:n]) + sum(disp[:n])) / n / 1e6


def idle_pct(ctx):
    s = ctx["run"].trace_summary
    return None if s is None else 100.0 * s.idle_share


def roofline_pct(ctx, kernel: str, per_call):
    """100 x (calls x least time of one call's work) / the kernel's
    device time in the window; ``per_call`` gives (ops, bytes)."""
    s = ctx["run"].trace_summary
    if s is None or not s.kernel_s.get(kernel):
        return None
    ops, nbytes = per_call
    least = s.kernel_count[kernel] * work.roofline_s(ops, nbytes, ctx["peak"])
    return 100.0 * least / s.kernel_s[kernel]


def serve_batch(ctx):
    """(rows, F, D, C) of one served batch; bulk batches are full."""
    c = ctx["cfg"]
    return (ctx["run"].traffic["max_batch"], c["features"], c["dim"],
            c["columns"])


def serve_mfu_pct(ctx):
    """100 x rows answered per second in the traced window x the
    operations of one row (``work.serve_row_ops``) / the bf16 peak."""
    run = ctx["run"]
    if run.trace_summary is None or "traced" not in run.counts:
        return None
    rate = run.counts["traced"]["rows"] / run.trace_summary.window_s
    return 100.0 * rate * work.serve_row_ops(ctx["cfg"]) / ctx["peak"][
        "bf16_flops"]
