"""Open-loop serving through ``OnlineEngine.serve``.

Requests of ``rows_min``..``rows_max`` rows arrive at ``rate_rps`` for
``--seconds`` (``gen.arrival_schedule``), each with a ``deadline_ms``
budget, and the engine batches them against its deadline planner
(``max_batch`` rows, ``depth`` batches in flight) through the
configuration's feature entry. Set-up builds the engine and serves a
few requests, which compiles every batch bucket.

Each request is timed by the benchmark from its scheduled arrival on
the engine's clock to the moment its answer is stored; ``p50_ms``,
``p95_ms`` and ``p99_ms`` are the 50th, 95th and 99th percentiles over
all requests of the window, and the cell reports those
``BENCHMARK.json`` names (the traced run's readers find them in the
result too). All three, the share of requests past their deadline and
how long the engine ran past the last arrival are printed beside it.
Every answer is
compared with the reference's answer for its rows; ``mismatch_ppm``
counts the distinct pool rows answered wrong at least once, per million
distinct rows answered.
"""
import gc
import time

import numpy as np

from bench import gen
from bench.modes import Result
from bench.harness import Window, timed

WARM_RID = 1 << 40


class _Stamped(dict):
    """The engine's response map, stamping when each answer arrives."""

    def __init__(self):
        super().__init__()
        self.at = {}

    def __setitem__(self, rid, value):
        self.at[rid] = time.perf_counter()
        super().__setitem__(rid, value)


def run(run, system) -> Result:
    from repro.serve import OnlineEngine, StreamingUpdater
    from repro.serve.stream import Arrival, OnlineRequest

    t = run.traffic
    with timed(run.phases, "pool"):
        pool = system.rows(t["pool_rows"])
    due, sizes, starts = gen.arrival_schedule(
        run.seed, seconds=run.seconds, rate_rps=t["rate_rps"],
        rows_min=t["rows_min"], rows_max=t["rows_max"],
        pool_rows=t["pool_rows"])
    events = [Arrival(t=float(due[i]), request=OnlineRequest(
        rid=i, feats=pool[starts[i]:starts[i] + sizes[i]],
        t_arrival=float(due[i]), deadline_ms=t["deadline_ms"]))
        for i in range(len(due))]
    engine = OnlineEngine(StreamingUpdater(system.model, system.artifact),
                          max_batch=t["max_batch"], depth=t["depth"],
                          fused=system.cfg.get("fused", False))
    with timed(run.phases, "warmup"):
        engine.serve([Arrival(t=0.001 * i, request=OnlineRequest(
            rid=WARM_RID + i, feats=pool[:t["rows_max"]],
            t_arrival=0.001 * i, deadline_ms=t["deadline_ms"]))
            for i in range(4)])
    stamped = _Stamped()
    engine.responses = stamped

    with Window(run) as w:
        with w.call("engine_serve"):
            report = engine.serve(events)
        w.add(requests=len(events), rows=int(sizes.sum()),
              batches=report["batches"])
    # The engine's clock zero, set after its own re-warm inside serve().
    t_zero = engine._t0

    system.artifact = system.model = engine = None
    gc.collect()
    want = system.answers(pool)
    lat_ms = []
    failed = 0
    seen = np.zeros(t["pool_rows"], bool)
    wrong = np.zeros(t["pool_rows"], bool)
    for i in range(len(due)):
        got = stamped.get(i)
        if got is None:
            failed += 1
            continue
        lat_ms.append((stamped.at[i] - (t_zero + due[i])) * 1e3)
        rows = slice(starts[i], starts[i] + sizes[i])
        seen[rows] = True
        wrong[rows] |= np.asarray(got) != want[rows]
    lat = np.asarray(lat_ms) if lat_ms else np.full(1, np.nan)
    tails = {f"p{q}_ms": float(np.percentile(lat, q)) for q in (50, 95, 99)}
    return Result(
        end_to_end=tails,
        notes=dict(tails,
                   past_deadline=float(np.mean(lat > t["deadline_ms"])),
                   drain_s=report["wall_s"] - run.seconds),
        attempted=len(due), failed=failed,
        checks={"missing_answers": failed,
                "mismatch_ppm": float(1e6 * wrong.sum() / max(seen.sum(), 1))})
