"""Fault-tolerant training driver.

Runs a real (CPU-scale here, pod-scale by construction) training loop
with the full production substrate:

  * deterministic checkpointable data pipeline (position in manifest)
  * atomic checkpoints + auto-resume from the newest *valid* one
  * a per-step wall-clock watchdog (straggler/hang mitigation: the step
    deadline triggers an emergency checkpoint + non-zero exit so the
    cluster manager can reschedule — the standard TPU-pod pattern)
  * optional simulated failure injection (--fail-at-step) used by the
    fault-tolerance tests to prove bit-exact resume.

Two trainer families run under the same driver:

  * the LM archs from ``repro.configs`` (per-step AdamW training), and
  * ``--arch memhd`` — the paper's QAIL trainer: one "step" is one
    scan-compiled device-resident epoch (``qail.qail_epoch_scan``), the
    checkpointed state is a ``MemhdTrainState``, and resume is bit-exact
    (asserted by tests/test_train_loop.py via the final AM digest).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m \
      --smoke --steps 50 --ckpt-dir /tmp/run1
  PYTHONPATH=src python -m repro.launch.train --arch memhd \
      --smoke --steps 10 --ckpt-dir /tmp/memhd_run
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import os
import signal
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache, obs

log = logging.getLogger("train")


def _event_log(cfg: "TrainRunConfig") -> obs.EventLog:
    """The run's JSONL event stream, next to the checkpoints: epoch /
    step stats, checkpoint write durations, watchdog fires, resumes —
    the machine-readable run history a dashboard tails live."""
    return obs.EventLog(os.path.join(cfg.ckpt_dir, "events.jsonl"))


@dataclasses.dataclass
class TrainRunConfig:
    arch: str = "mamba2-130m"
    smoke: bool = True
    steps: int = 100
    seq_len: int = 256
    global_batch: int = 8
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_every: int = 20
    keep: int = 3
    lr: float = 3e-4
    warmup: int = 20
    log_every: int = 10
    step_deadline_s: float = 300.0
    fail_at_step: int = -1  # fault-injection for tests
    seed: int = 0
    log_json: bool = False  # structured one-JSON-per-line logging


class StepWatchdog:
    """SIGALRM-based per-step deadline (single-host stand-in for the
    pod-level heartbeat/reschedule machinery)."""

    def __init__(self, deadline_s: float, on_timeout):
        self.deadline = deadline_s
        self.on_timeout = on_timeout

    def __enter__(self):
        def handler(signum, frame):
            self.on_timeout()
            raise TimeoutError("train step exceeded deadline")

        self._prev = signal.signal(signal.SIGALRM, handler)
        signal.setitimer(signal.ITIMER_REAL, self.deadline)
        return self

    def __exit__(self, *exc):
        signal.setitimer(signal.ITIMER_REAL, 0.0)
        signal.signal(signal.SIGALRM, self._prev)
        return False


def run_memhd(cfg: TrainRunConfig) -> dict:
    """QAIL training under the fault-tolerant driver.

    One driver "step" == one scan-compiled QAIL epoch (a single device
    dispatch; the per-epoch ``float(miss)`` is the only host sync). The
    dataset, encoder and clustering init are deterministic in
    ``cfg.seed``, so a restore of the newest ``MemhdTrainState``
    continues the run bit-exactly — the returned ``am_digest`` (sha256
    of the binary AM) is identical with and without a mid-run crash.
    """
    import hashlib

    from repro.checkpoint import CheckpointConfig, CheckpointManager
    from repro.core import (
        EncoderConfig, MemhdConfig, MemhdModel, encoding, qail,
    )
    from repro.core.memhd import MemhdTrainState
    from repro.data import load_dataset

    if cfg.smoke:
        ds = load_dataset("mnist", train_per_class=120, test_per_class=30,
                          seed=cfg.seed)
        enc = EncoderConfig(kind="projection", features=ds.features,
                            dim=256)
        amc = MemhdConfig(dim=256, columns=64, classes=ds.classes,
                          kmeans_iters=8, lr=0.02, batch_size=256,
                          seed=cfg.seed)
    else:
        ds = load_dataset("mnist", train_per_class=1000,
                          test_per_class=200, seed=cfg.seed)
        enc = EncoderConfig(kind="projection", features=ds.features,
                            dim=512)
        amc = MemhdConfig(dim=512, columns=128, classes=ds.classes,
                          kmeans_iters=25, lr=0.02, batch_size=256,
                          seed=cfg.seed)

    model = MemhdModel.create(jax.random.key(cfg.seed), enc, amc)
    h = model.encode(ds.train_x)
    q = encoding.binarize_query(h)
    n = h.shape[0]
    epochs = cfg.steps

    ckpt = CheckpointManager(CheckpointConfig(cfg.ckpt_dir, keep=cfg.keep))
    events = _event_log(cfg)

    def timed_save(step, tree, extra):
        t0 = time.perf_counter()
        ckpt.save(step, tree, extra=extra)
        events.emit("checkpoint", step=step,
                    dur_s=round(time.perf_counter() - t0, 4),
                    emergency=bool(extra.get("emergency", False)))

    template = MemhdTrainState.create(model.am_state)
    restored_epoch, tree, extra = ckpt.restore(template)
    miss_hist = []
    if restored_epoch is not None:
        state = jax.tree.map(jnp.asarray, tree.am_state)
        start_epoch = restored_epoch
        miss_hist = list(extra.get("miss", []))
        log.info("resumed memhd from epoch %d", start_epoch)
        events.emit("resume", step=start_epoch)
    else:
        m_init, _ = model.initialize_am(jax.random.key(cfg.seed + 1),
                                        ds.train_x, ds.train_y, h=h, q=q)
        state = m_init.am_state
        start_epoch = 0
        timed_save(0, MemhdTrainState.create(state, 0),
                   extra={"miss": miss_hist})

    hb, qb, yb, mask = qail.prebatch(h, q, ds.train_y, amc.batch_size)
    # Emergency-checkpoint source: a HOST (numpy) snapshot of the last
    # completed epoch. The device state is donated into the in-flight
    # scan on accelerator backends, so a live reference would be a dead
    # buffer exactly when the watchdog needs it. The AM is a few KB —
    # the per-epoch snapshot cost is noise next to the epoch itself.
    last_state = [jax.tree.map(np.asarray, state)]

    def emergency_ckpt():
        log.error("watchdog fired: writing emergency memhd checkpoint")
        events.emit("watchdog", step=last_epoch[0],
                    deadline_s=cfg.step_deadline_s)
        timed_save(last_epoch[0],
                   MemhdTrainState.create(last_state[0], last_epoch[0]),
                   extra={"miss": miss_hist, "emergency": True})

    last_epoch = [start_epoch]
    t_start = time.time()
    for ep in range(start_epoch, epochs):
        t_ep = time.perf_counter()
        with StepWatchdog(cfg.step_deadline_s, emergency_ckpt):
            with obs.span("qail_epoch", epoch=ep):
                state, n_miss = qail.qail_epoch_scan(state, amc, hb, qb,
                                                     yb, mask)
        miss_rate = float(n_miss) / n  # the one host sync this epoch
        dur_s = time.perf_counter() - t_ep
        miss_hist.append(miss_rate)
        last_state[0] = jax.tree.map(np.asarray, state)
        last_epoch[0] = ep + 1
        events.emit("epoch", step=ep + 1, miss=round(miss_rate, 6),
                    dur_s=round(dur_s, 4),
                    samples_per_sec=round(n / dur_s, 1) if dur_s else None)
        if (ep + 1) % cfg.log_every == 0:
            log.info("epoch %d miss %.4f (%.2f s/epoch)", ep + 1,
                     miss_rate,
                     (time.time() - t_start) / (ep + 1 - start_epoch))
        if (ep + 1) % cfg.ckpt_every == 0 or ep + 1 == epochs:
            timed_save(ep + 1, MemhdTrainState.create(state, ep + 1),
                       extra={"miss": miss_hist})
        if cfg.fail_at_step == ep + 1:
            log.error("injected failure at epoch %d", ep + 1)
            events.emit("injected_failure", step=ep + 1)
            os._exit(42)  # simulate a hard node death

    trained = dataclasses.replace(model, am_state=state)
    eval_acc = trained.score(ds.test_x, ds.test_y)
    digest = hashlib.sha256(
        np.asarray(state["binary"]).tobytes()).hexdigest()
    dt = time.time() - t_start
    events.emit("run_end", steps_run=epochs - start_epoch,
                resumed_from=start_epoch, eval_acc=eval_acc,
                wall_s=round(dt, 3), compiles=obs.jaxmon.compiles())
    events.close()
    return {
        "first_miss": miss_hist[0] if miss_hist else None,
        "last_miss": miss_hist[-1] if miss_hist else None,
        "steps_run": epochs - start_epoch,
        "resumed_from": start_epoch,
        "eval_acc": eval_acc,
        "am_digest": digest,
        "samples_per_sec": (n * (epochs - start_epoch) / dt
                            if dt > 0 and epochs > start_epoch else None),
    }


# Non-LM trainers that run under the same fault-tolerant driver.
TRAINERS = {"memhd": run_memhd}


def run(cfg: TrainRunConfig) -> dict:
    if cfg.arch in TRAINERS:
        return TRAINERS[cfg.arch](cfg)

    from repro.checkpoint import CheckpointConfig, CheckpointManager
    from repro.configs import get_config, get_smoke_config
    from repro.data.lm import LmDataConfig, PipelineState, next_batch
    from repro.distributed.steps import init_train_state, make_train_step
    from repro.optim import AdamWConfig, ScheduleConfig, make_schedule

    mcfg = (get_smoke_config(cfg.arch) if cfg.smoke
            else get_config(cfg.arch))
    if mcfg.frontend != "none":
        raise SystemExit(
            f"{cfg.arch} needs modality inputs; use examples/ drivers")

    opt_cfg = AdamWConfig(lr=cfg.lr)
    sched = make_schedule(ScheduleConfig(
        warmup_steps=cfg.warmup, total_steps=cfg.steps))
    dcfg = LmDataConfig(vocab_size=mcfg.vocab_size, seq_len=cfg.seq_len,
                        global_batch=cfg.global_batch)

    params, opt_state, _axes = init_train_state(
        jax.random.key(cfg.seed), mcfg, opt_cfg)
    pipe = PipelineState(seed=cfg.seed)
    start_step = 0

    ckpt = CheckpointManager(CheckpointConfig(cfg.ckpt_dir, keep=cfg.keep))
    events = _event_log(cfg)

    def timed_save(step, tree, extra):
        t0 = time.perf_counter()
        ckpt.save(step, tree, extra=extra)
        events.emit("checkpoint", step=step,
                    dur_s=round(time.perf_counter() - t0, 4),
                    emergency=bool(extra.get("emergency", False)))

    restored_step, tree, extra = ckpt.restore(
        {"params": params, "opt": opt_state})
    if restored_step is not None:
        params, opt_state = tree["params"], tree["opt"]
        params = jax.tree.map(jnp.asarray, params)
        opt_state = jax.tree.map(jnp.asarray, opt_state)
        pipe = PipelineState.from_json(extra["pipeline"])
        start_step = restored_step
        log.info("resumed from step %d", start_step)
        events.emit("resume", step=start_step)

    step_fn = jax.jit(make_train_step(mcfg, opt_cfg, sched))

    def emergency_ckpt():
        log.error("watchdog fired: writing emergency checkpoint")
        events.emit("watchdog", step=last_step[0],
                    deadline_s=cfg.step_deadline_s)
        timed_save(last_step[0], {"params": params, "opt": opt_state},
                   extra={"pipeline": pipe.to_json(), "emergency": True})

    last_step = [start_step]
    losses = []
    t_start = time.time()
    for step in range(start_step, cfg.steps):
        t_step = time.perf_counter()
        batch_np, pipe = next_batch(dcfg, pipe)
        batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
        with StepWatchdog(cfg.step_deadline_s, emergency_ckpt):
            with obs.span("train_step", step=step):
                params, opt_state, metrics = step_fn(
                    params, opt_state, batch,
                    jnp.asarray(step, jnp.int32))
        loss = float(metrics["loss"])
        losses.append(loss)
        last_step[0] = step + 1
        if not np.isfinite(loss):
            events.emit("diverged", step=step, loss=loss)
            raise FloatingPointError(f"loss diverged at step {step}")
        if (step + 1) % cfg.log_every == 0:
            dt_step = time.perf_counter() - t_step
            log.info("step %d loss %.4f (%.2f s/step)", step + 1, loss,
                     (time.time() - t_start) / (step + 1 - start_step))
            events.emit("step", step=step + 1, loss=round(loss, 6),
                        dur_s=round(dt_step, 4),
                        tokens_per_sec=round(
                            cfg.global_batch * cfg.seq_len / dt_step, 1)
                        if dt_step else None)
        if (step + 1) % cfg.ckpt_every == 0 or step + 1 == cfg.steps:
            timed_save(step + 1, {"params": params, "opt": opt_state},
                       extra={"pipeline": pipe.to_json()})
        if cfg.fail_at_step == step + 1:
            log.error("injected failure at step %d", step + 1)
            events.emit("injected_failure", step=step + 1)
            os._exit(42)  # simulate a hard node death

    events.emit("run_end", steps_run=len(losses),
                resumed_from=start_step,
                wall_s=round(time.time() - t_start, 3),
                compiles=obs.jaxmon.compiles())
    events.close()
    return {
        "first_loss": losses[0] if losses else None,
        "last_loss": losses[-1] if losses else None,
        "steps_run": len(losses),
        "resumed_from": start_step,
    }


def main():
    ap = argparse.ArgumentParser()
    for f in dataclasses.fields(TrainRunConfig):
        name = "--" + f.name.replace("_", "-")
        if f.type == "bool" or isinstance(f.default, bool):
            ap.add_argument(name, action="store_true", default=f.default)
        else:
            ap.add_argument(name, type=type(f.default), default=f.default)
    args = ap.parse_args()
    cfg = TrainRunConfig(**{f.name: getattr(args, f.name)
                            for f in dataclasses.fields(TrainRunConfig)})
    obs.setup_logging(json_mode=cfg.log_json)
    compile_cache.enable()
    obs.install()  # jit compile counters for the run_end event
    out = run(cfg)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
