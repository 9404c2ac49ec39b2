"""The program's own spans, for the per-layer readers in ``bench/metrics``.

The program records its spans in ``repro.obs.trace.TRACER``. The
harness clears that recorder when the window opens, and nothing records
into it after the window closes, so when the readers run it holds the
window's spans, whole (the profiler's trace covers only its first
seconds). A program without a span or argument a reader needs gives the
reader nothing to read: it returns None and does not raise.
"""


def events():
    """The window's span events (``name``, ``dur_ns``, ``span_id``,
    ``parent_id`` and ``args``, a dict or None)."""
    from repro.obs import trace
    return trace.TRACER.events()


def ratio_of_args(evs, name, num, den):
    """Sum of argument ``num`` over sum of ``den`` across the spans
    called ``name`` that carry both, or None."""
    total = count = 0
    for e in evs:
        a = e.args if e.name == name else None
        if a and num in a and den in a:
            total += a[num]
            count += a[den]
    return total / count if count else None


def self_ms_per_child(evs, name, minus, per):
    """Milliseconds of the spans called ``name``, less their children
    called ``minus``, over the number of their children called ``per``;
    or None."""
    parents = {e.span_id: e.dur_ns for e in evs if e.name == name}
    less = sum(e.dur_ns for e in evs
               if e.name == minus and e.parent_id in parents)
    n = sum(1 for e in evs if e.name == per and e.parent_id in parents)
    return (sum(parents.values()) - less) / n / 1e6 if n else None
