"""A run with the timed path broken underneath comes out not correct.

Each test drives a whole small run of a cell on the CPU through
``run.run_cell`` (the look for a chip skipped) with one fault planted
where the program produces its result, and sees ``correct`` false;
the same run unbroken is correct.
"""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import work

from .test_harness import _run_py

SMALL = {"features": 64, "dim": 128}


def small_cell(name):
    run = _run_py()
    c = run.load_cell(name)
    c["cfg"] = dict(c["cfg"], **SMALL)
    if c["cfg"]["system"] == "planted_hierarchical":
        c["cfg"].update(columns=3000, classes=3000,
                        deploy={"groups": 12, "shortlist": 3})
    else:
        c["cfg"].update(columns=64, data=dict(c["cfg"]["data"],
                                              train_rows=1200),
                        qail=dict(c["cfg"]["qail"], batch_size=48))
    t = dict(c["traffic"])
    t.update({k: v for k, v in dict(pool_rows=2048, requests_per_call=32,
                                    rate_rps=100.0).items() if k in t})
    c["traffic"] = t
    return run, c


@pytest.fixture(autouse=True)
def fresh(monkeypatch):
    # Programs traced before a fault is planted would hide it.
    jax.clear_caches()
    peaks = work.peaks
    monkeypatch.setattr(work, "peaks", lambda kind: peaks("TPU v5 lite"))
    yield
    jax.clear_caches()


def run_small(name, seconds=0.3):
    run, c = small_cell(name)
    line, _ = run.run_cell(c, 2**31 + 99, seconds, False,
                           time.perf_counter(), jax.devices())
    return line


def _alter_first(pred):
    return pred.at[0].set(pred[0] + 1)


@pytest.mark.parametrize("cell", ["mnist1024.bulk", "mnist1024.online",
                                  "hier131k.bulk", "mnist1024.train"])
def test_sound_run_is_correct(cell):
    assert run_small(cell)["correct"]


@pytest.mark.parametrize("cell", ["mnist1024.bulk", "mnist1024.online"])
def test_altered_answer_flat(cell, monkeypatch):
    from repro.kernels import ops
    real = ops.predict_from_features
    monkeypatch.setattr(ops, "predict_from_features",
                        lambda *a, **k: _alter_first(real(*a, **k)))
    line = run_small(cell)
    assert not line["correct"]
    assert line["checks"]["mismatch_ppm"]["value"] > 0


def test_altered_answer_hierarchical(monkeypatch):
    from repro.deploy.hierarchical import HierarchicalMemhd
    real = HierarchicalMemhd.predict_query
    monkeypatch.setattr(HierarchicalMemhd, "predict_query",
                        lambda self, q: _alter_first(real(self, q)))
    line = run_small("hier131k.bulk")
    assert not line["correct"]


def test_train_state_unchanged(monkeypatch):
    from repro.core import qail
    real = qail.qail_epoch_scan

    def frozen(state, *a, **k):
        _, miss = real(jax.tree.map(jnp.copy, state), *a, **k)
        return state, miss
    monkeypatch.setattr(qail, "qail_epoch_scan", frozen)
    line = run_small("mnist1024.train")
    assert not line["correct"]
    assert line["checks"]["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_train_half_batch_left_out(monkeypatch):
    from repro.kernels import ops
    real = ops.qail_update

    def half(q, upd, am_t, cc, labels, mask, **k):
        keep = jnp.arange(mask.shape[0]) < mask.shape[0] // 2
        return real(q, upd, am_t, cc, labels, mask * keep, **k)
    monkeypatch.setattr(ops, "qail_update", half)
    assert not run_small("mnist1024.train")["correct"]
