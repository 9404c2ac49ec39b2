"""The online cell reports its median latency end to end and its 95th
percentile per layer, both over every request of the window."""
import time

import jax

from .test_faults import small_cell
from .test_harness import _run_py


def test_online_reports_median_and_prints_tails():
    run, c = small_cell("mnist1024.online")
    line, notes = run.run_cell(c, 2**31 + 7, 0.3, False, time.perf_counter(),
                               jax.devices())
    assert set(line["metrics"]) == {"p50_ms", "setup_s"}
    printed = {n.split()[1]: float(n.split()[2]) for n in notes
               if n.startswith("window p")}
    assert printed["p50_ms"] == line["metrics"]["p50_ms"]["value"]
    assert 0 < printed["p50_ms"] <= printed["p95_ms"] <= printed["p99_ms"]


def test_p95_reader_takes_the_modes_tail():
    read = _run_py().reader("p95_ms.online")
    assert read({"end_to_end": {"p50_ms": 6.3, "p95_ms": 9.6}}) == 9.6
    assert read({"end_to_end": {"rows_per_s": 1.0}}) is None
