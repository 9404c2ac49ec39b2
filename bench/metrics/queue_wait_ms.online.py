"""serve/engine: the mean milliseconds a request waited from its arrival
to its batch's dispatch (the planner's hold), from the ``wait_ms_sum``
and ``requests`` arguments of the engine's ``dispatch`` spans."""
from bench import spans


def read(ctx):
    return spans.ratio_of_args(spans.events(), "dispatch", "wait_ms_sum",
                               "requests")
