"""QAIL training: ``MemhdModel.fit`` called for ``epochs_per_call``
epochs at a time, each call continuing from the model the last returned.

Set-up makes the training set and the initial AM from the seed and
makes the first call, which compiles every program the window uses:
that call is the one checked. The reference follows the same epochs
from the same initial AM; compared are each epoch's miss count, the
binary AM cells that differ after the call, and the gap between the
norms of the float AM's change (program against reference, over the
reference's). The window then runs whole calls until ``--seconds`` have
passed; ``train_samples_per_s`` is the samples of the epochs completed,
host syncs included, over the window's length.
"""
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference
from bench.modes import Result
from bench.harness import Window, timed


def run(run, system) -> Result:
    t = run.traffic
    x, y, fp0, owners, model = system.train
    epochs = t["epochs_per_call"]
    fit = dict(init_method="keep", epochs=epochs, use_kernel=True,
               mode="batched")
    key = jax.random.key(0)
    # fit donates the model's AM state (the float AM and the owners).
    fp_start, owners = np.asarray(fp0), np.asarray(owners)

    with timed(run.phases, "first_call"):
        model, hist = model.fit(key, x, y, **fit)
    checked = {k: np.asarray(model.am_state[k]) for k in ("fp", "binary")}
    n = int(x.shape[0])
    misses = [round(r["train_miss"] * n) for r in hist["curve"]]

    calls = 0
    with Window(run) as w:
        deadline = w.t0 + run.seconds
        while time.perf_counter() < deadline:
            with w.call("fit"):
                model, hist = model.fit(key, x, y, **fit)
                # fit's history pulls each epoch's miss count: the host
                # has synced by the time it returns.
                jax.block_until_ready(model.am_state)
            w.add(samples=epochs * n)
            calls += 1

    system.artifact = system.model = model = None
    system.train = None
    gc.collect()
    q = system.cfg["qail"]
    fp_r, bin_r, miss_r = reference.qail(
        jnp.asarray(fp_start), jnp.asarray(owners), x, y, system.proj, epochs=epochs,
        batch=q["batch_size"], lr=q["lr"])
    fp_r, bin_r = np.asarray(fp_r), np.asarray(bin_r)
    ref_change = float(np.linalg.norm(fp_r - fp_start))
    got_change = float(np.linalg.norm(checked["fp"] - fp_start))
    return Result(
        end_to_end={"train_samples_per_s": run.counts["samples"]
                    / run.window_s},
        attempted=calls * epochs, failed=0,
        checks={
            "miss_gap": float(max(abs(a - int(b)) for a, b in
                                  zip(misses, np.asarray(miss_r)))),
            "am_bits_differ": float((checked["binary"] != bin_r).sum()),
            "change_norm_gap": abs(got_change - ref_change)
            / max(ref_change, 1e-30)})
