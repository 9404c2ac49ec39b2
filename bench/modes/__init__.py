"""One mode per kind of traffic, named by the traffic file's ``mode``.

``run(run, system)`` sets up, measures one window and checks what the
timed path produced; it returns a ``bench.modes.Result``.
"""
import dataclasses


@dataclasses.dataclass
class Result:
    end_to_end: dict        # end-to-end metric name -> value
    attempted: int
    failed: int
    checks: dict            # number compared for `correct` -> value
    notes: dict = dataclasses.field(default_factory=dict)  # printed only
