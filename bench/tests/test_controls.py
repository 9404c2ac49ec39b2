"""The control: the reference put in the program's place, computed in a
lower precision than the configuration states, comes out as not
correct, on a whole small run of the cell (the look for a chip skipped).

``bench/control.py`` runs the same at the cells' own sizes on the chip
(the readings are in PERF.md); here the sizes are cut so that the CPU can hold them, with the widths F
and D kept. The hierarchical cell's answers (one class per centroid, many
near ties) fail at ``HIGH``, three bf16 passes. The flat cells' answers
(10 classes) and the training numbers do not move at ``HIGH``; they
fail at ``DEFAULT``, one pass.
"""

import jax
import pytest

from bench import control

from .test_faults import fresh, run_small, small_cell  # noqa: F401

HIGH = jax.lax.Precision.HIGH
DEFAULT = jax.lax.Precision.DEFAULT


def _big_widths(name):
    run, c = small_cell(name)
    c["cfg"] = dict(c["cfg"], features=784, dim=1024)
    return run, c


@pytest.mark.parametrize("cell,precision", [
    ("hier131k.bulk", HIGH), ("mnist1024.bulk", DEFAULT),
    ("mnist1024.online", DEFAULT), ("mnist1024.train", DEFAULT)])
def test_control_is_not_correct(cell, precision, monkeypatch):
    import time
    control.in_place(precision, monkeypatch.setattr)
    run, c = _big_widths(cell)
    if c["traffic"].get("pool_rows"):
        c["traffic"] = dict(c["traffic"], pool_rows=32768,
                            requests_per_call=512, rate_rps=2000.0)
        c["traffic"] = {k: v for k, v in c["traffic"].items()
                        if k in small_cell(cell)[1]["traffic"]}
    line, _ = run.run_cell(c, 2**31 + 11, 0.5, False, time.perf_counter(),
                           jax.devices())
    assert not line["correct"], line["checks"]
