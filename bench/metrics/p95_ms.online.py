"""serve/engine: the 95th percentile of request latency over the window,
from scheduled arrival to stored answer, as the online mode measures it
(``bench/modes/online.py``). A host stall of a second or more reaches it
in a 30 s window, so it is read here rather than bounded end to end."""


def read(ctx):
    return ctx["end_to_end"].get("p95_ms")
