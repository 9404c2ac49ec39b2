"""Offline classification: ``serve_batches`` called in a loop.

Each call serves ``requests_per_call`` requests of ``request_rows`` rows,
drawn in a seeded order from a pool of ``pool_rows`` rows, through the
configuration's feature entry (``predict_features``), ``max_batch`` rows
a batch and ``depth`` batches in flight. The batch shapes are warmed
during set-up; the window runs whole calls until ``--seconds`` have
passed. ``rows_per_s`` is the rows answered over the window's length.

Every answer of every call is compared with the reference's answer for
its rows; ``mismatch_ppm`` counts the distinct pool rows answered wrong
at least once, per million distinct rows answered.
"""
import gc
import time

import numpy as np

from bench import gen
from bench.modes import Result
from bench.harness import Window, timed

ORDERS = 16  # distinct request orders, cycled over the calls


def run(run, system) -> Result:
    from repro.launch.serve_memhd import Request, serve_batches

    t = run.traffic
    rows_per_req = t["request_rows"]
    with timed(run.phases, "pool"):
        pool = system.rows(t["pool_rows"])
    n_req = t["pool_rows"] // rows_per_req
    requests = [Request(rid=i, feats=pool[i * rows_per_req:
                                          (i + 1) * rows_per_req])
                for i in range(n_req)]
    orders = [gen.bulk_order(run.seed, k, n_req)[:t["requests_per_call"]]
              for k in range(ORDERS)]
    fused = system.cfg.get("fused", False)
    opts = dict(max_batch=t["max_batch"], fused=fused, depth=t["depth"])
    if (t["requests_per_call"] * rows_per_req) % t["max_batch"]:
        raise ValueError("bulk calls must fill every batch")
    with timed(run.phases, "warmup"):
        serve_batches(system.artifact, [requests[i] for i in orders[0]],
                      warmup=True, **opts)

    served = []  # (order index, responses)
    with Window(run) as w:
        deadline = w.t0 + run.seconds
        call = 0
        while time.perf_counter() < deadline:
            k = call % ORDERS
            with w.call("serve_batches"):
                responses, stats = serve_batches(
                    system.artifact, [requests[i] for i in orders[k]],
                    warmup=False, **opts)
            served.append((k, responses))
            w.add(rows=stats["rows_real"], calls=1)
            call += 1

    system.artifact = system.model = None
    gc.collect()
    want = system.answers(pool).reshape(n_req, rows_per_req)
    attempted = failed = 0
    seen = np.zeros((n_req, rows_per_req), bool)
    wrong = np.zeros((n_req, rows_per_req), bool)
    for k, responses in served:
        for i in orders[k]:
            attempted += 1
            got = responses.get(int(i))
            if got is None:
                failed += 1
                continue
            seen[i] = True
            wrong[i] |= np.asarray(got) != want[i]
    return Result(
        end_to_end={"rows_per_s": run.counts["rows"] / run.window_s},
        attempted=attempted, failed=failed,
        checks={"missing_answers": failed,
                "mismatch_ppm": float(1e6 * wrong.sum() / max(seen.sum(), 1))})
