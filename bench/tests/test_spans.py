"""The readers of the program's spans (``bench/spans.py``), on synthetic
span events: what each reads, and nothing where the program records no
such span or argument."""
from types import SimpleNamespace

import pytest

from bench import spans

from .test_harness import _run_py

MS = 1_000_000  # ns


def ev(name, dur_ms, span_id, parent_id=0, **args):
    return SimpleNamespace(name=name, dur_ns=int(dur_ms * MS),
                           span_id=span_id, parent_id=parent_id,
                           args=args or None)


def read(metric, evs, monkeypatch):
    monkeypatch.setattr(spans, "events", lambda: evs)
    return _run_py().reader(metric)({})


def test_queue_wait_is_the_mean_over_requests(monkeypatch):
    evs = [ev("dispatch", 0.1, 1, batch=0, rows=8, requests=2,
              wait_ms_sum=6.0, wait_ms_max=4.0),
           ev("device_wait", 0.05, 2, batch=0),
           ev("dispatch", 0.1, 3, batch=1, rows=8, requests=4,
              wait_ms_sum=4.0, wait_ms_max=2.0),
           ev("host_prep", 0.2, 4, batch=2, requests=9)]
    assert read("queue_wait_ms.online", evs, monkeypatch) == pytest.approx(
        10.0 / 6)


def test_fit_host_is_fit_less_its_syncs_per_epoch(monkeypatch):
    evs = [ev("fit.encode", 1.0, 2, 1),
           ev("fit.epoch", 0.5, 3, 1, epoch=1),
           ev("fit.sync", 4.0, 4, 1, epoch=1),
           ev("fit.epoch", 0.5, 5, 1, epoch=2),
           ev("fit.sync", 4.0, 6, 1, epoch=2),
           ev("fit", 11.0, 1),
           ev("fit.epoch", 0.5, 8, 7, epoch=1),
           ev("fit.sync", 3.0, 9, 7, epoch=1),
           ev("fit", 5.0, 7),
           # A sync outside any fit is not the fit's.
           ev("fit.sync", 50.0, 10)]
    assert read("fit_host_ms.train", evs, monkeypatch) == pytest.approx(
        (11.0 + 5.0 - 8.0 - 3.0) / 3)


# What a program without these spans' arguments records: ``dispatch``
# without wait arguments, no ``fit`` spans.
OLD_PROGRAM = [ev("host_prep", 0.2, 1, requests=3),
               ev("pad", 0.1, 2, 1, rows=8, bucket=8),
               ev("dispatch", 0.3, 3, rows=8),
               ev("device_wait", 0.5, 4, rows=8)]


@pytest.mark.parametrize("metric", ["queue_wait_ms.online",
                                    "fit_host_ms.train"])
@pytest.mark.parametrize("evs", [OLD_PROGRAM, []], ids=["old", "empty"])
def test_nothing_to_read_is_none(metric, evs, monkeypatch):
    assert read(metric, evs, monkeypatch) is None
