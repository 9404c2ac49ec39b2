"""Multi-device serving scaling sweep: aggregate QPS vs device count.

Sweeps data meshes of 1, 2, 4, 8 devices (as many as the process has) x
{packed, imc} deployment backends through the REAL serving stack
(``ShardedArtifact`` under the ``serve_batches`` double-buffered driver)
at a fixed per-device row budget (weak scaling). Every point runs in
this one process over the devices JAX already sees, so the sweep never
starts a child that would need a chip the parent holds.

Each point reports the measured wall-clock rate and the device it ran
on, and asserts bit-exactness of the sharded predictions vs the
single-device artifact and a communication-free compiled program (no
collectives in the HLO). No speed-up is asserted: host-emulated devices
(``--xla_force_host_platform_device_count``) run their partitions one
after another, and their rate is not a device number.

Usage:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src:. python -m benchmarks.serve_scaling
"""
from __future__ import annotations

import json

DEVICE_COUNTS = (1, 2, 4, 8)
BACKENDS = ("packed", "imc")
ROWS_PER_DEVICE = 64
N_BATCHES = 12
FEATURES, DIM, COLUMNS, CLASSES = 64, 128, 128, 10


def _build_model():
    """An untrained model with a random AM — throughput needs no fit."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import EncoderConfig, MemhdConfig, MemhdModel
    from repro.core import am as am_lib

    enc = EncoderConfig(kind="projection", features=FEATURES, dim=DIM)
    amc = MemhdConfig(dim=DIM, columns=COLUMNS, classes=CLASSES)
    model = MemhdModel.create(jax.random.key(0), enc, amc)
    rng = np.random.default_rng(0)
    fp = jnp.asarray(rng.normal(size=(COLUMNS, DIM)).astype(np.float32))
    owners = jnp.asarray(np.arange(COLUMNS) % CLASSES, np.int32)
    state = am_lib.make_am_state(fp, owners, amc.threshold)
    return dataclasses.replace(model, am_state=state)


def _run_point(model, n_devices: int, backend: str) -> dict:
    """One sweep point: a data mesh over the first ``n_devices``."""
    import time

    import jax
    import numpy as np

    from repro.deploy import ShardedArtifact
    from repro.launch.serve_memhd import Request, serve_batches

    dep = model.deploy(target=backend)
    sharded = ShardedArtifact(dep, devices=n_devices)

    rows = ROWS_PER_DEVICE * n_devices
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, feats=rng.normal(
        size=(rows, FEATURES)).astype(np.float32))
        for i in range(N_BATCHES)]

    # Bit-exactness of the sharded path vs the plain artifact.
    probe = reqs[0].feats[: ROWS_PER_DEVICE * n_devices - 3]  # ragged
    bit_exact = bool((np.asarray(sharded.predict(probe))
                      == np.asarray(dep.predict(probe))).all())

    # The serving program must be communication-free: rows are
    # independent, so any collective is pure overhead.
    lowered = sharded._sharded_fn("predict").lower(
        sharded.artifact, reqs[0].feats)
    hlo = lowered.compile().as_text().lower()
    collectives = any(tok in hlo for tok in
                      ("all-reduce", "collective-permute", "all-to-all",
                       "all-gather", "reduce-scatter"))

    serve_batches(sharded, reqs, max_batch=rows)  # warmup/compile
    t0 = time.perf_counter()
    responses, stats = serve_batches(sharded, reqs, max_batch=rows,
                                     warmup=False, depth=2)
    wall = time.perf_counter() - t0
    assert len(responses) == N_BATCHES
    total_rows = N_BATCHES * rows
    dev = jax.devices()[0]
    return {
        "backend": backend,
        "devices": n_devices,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "rows": total_rows,
        "wall_s": round(wall, 4),
        "lat_ms_p50": stats["lat_ms_p50"],
        "qps": round(total_rows / wall, 1),
        "bit_exact": bit_exact,
        "collectives": collectives,
    }


def main() -> None:
    import jax

    model = _build_model()
    counts = [n for n in DEVICE_COUNTS if n <= jax.device_count()]
    for backend in BACKENDS:
        for n in counts:
            rep = _run_point(model, n, backend)
            us = rep["wall_s"] / N_BATCHES * 1e6
            print(f"serve_scaling/{backend}_d{n},{us:.0f},"
                  f"qps={rep['qps']:.0f} on {rep['device_kind']}",
                  flush=True)
            print("RESULT " + json.dumps(rep), flush=True)
            assert rep["bit_exact"], (
                f"sharded {backend} d={n} not bit-exact")
            assert not rep["collectives"], (
                f"serving program has collectives at {backend} d={n}")


if __name__ == "__main__":
    main()
