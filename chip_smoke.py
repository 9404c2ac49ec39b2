#!/usr/bin/env python3
"""Drive the MEMHD main path once on a TPU and check what comes out.

With no arguments it needs one TPU chip and runs, in this one process:

* ``train``  — ``MemhdModel.fit`` at the paper's MNIST 1024x1024 point
  for a few epochs, once on the XLA path and once through the Pallas
  ``qail_update`` kernel; the two must agree as tests/test_qail_engine.py
  requires (binary AM equal, float AM within 1e-5, equal miss counts).
* ``serve``  — every deployment backend (packed popcount and unpack,
  staged and fused; unpacked; imc at an 8-bit ADC; multibit at 2 and 4
  bits; hierarchical) through ``serve_batches`` on a ragged stream, at
  1024x1024 and at the ISOLET flagship 512x128. Predictions must equal
  the same artifact's oracle path (the ``ref.py`` semantics) run on the
  chip, every served kernel must dispatch as Pallas, and the steady-state
  pass must compile nothing.
* ``hierarchical_at_scale`` — a planted 100k-centroid AM deployed with
  ``target="hierarchical"``: served predictions equal the oracle, and
  the S = G configuration equals the flat packed scan bit for bit.
* ``online`` — a short ``launch/serve_online`` stream with a drift fold
  and a live class append: generations ``[shape-stable, not]`` and no
  steady-state recompiles.

Each phase prints one JSON line; a phase that fails ends the run with
a non-zero exit. The last line is ``{"ok": true, "device": {...}}``.
Without a TPU (for example under ``JAX_PLATFORMS=cpu``) it exits 1
before running anything.

``--chips 4`` runs only the multi-chip check instead: ``ShardedArtifact``
serving over four chips for the packed and hierarchical backends,
compared bit for bit with the one-chip artifact, with the output shards
on four distinct devices and no collective in the compiled program.

Usage:
  python chip_smoke.py [--seed 0]
  python chip_smoke.py --chips 4
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import io
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

MAX_BATCH = 1024
DEPTH = 2
N_REQUESTS = 256
MAX_ROWS = 32
TRAIN_EPOCHS = 3
# Planted huge label space: C centroids around G_PLANT prototypes,
# indexed with G groups and an S-cluster shortlist
# (benchmarks/hierarchical_search.py's C = 100k point).
HIER_C, HIER_G_PLANT, HIER_G, HIER_S = 100_000, 316, 448, 8
ANCHOR_QUERIES = 16
COLLECTIVES = ("all-reduce", "collective-permute", "all-to-all",
               "all-gather", "reduce-scatter")


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# -- helpers ------------------------------------------------------------------

def device_fields() -> dict:
    """The device object of the last line: ``obs.device_info()`` with
    ``kind`` for ``device_kind``. Phase lines carry ``device_info()``
    itself, as the serve report does."""
    from repro.obs import device_info

    dev = device_info()
    return {"platform": dev["platform"], "kind": dev["device_kind"],
            "count": dev["count"]}


def tier_delta(before: dict, after: dict) -> dict:
    """Dispatch counts that ``after`` adds to ``before``, per kernel."""
    out = {}
    for kernel, tiers in after.items():
        for tier, n in tiers.items():
            d = n - before.get(kernel, {}).get(tier, 0)
            if d:
                out.setdefault(kernel, {})[tier] = d
    return out


def only_pallas(tiers: dict) -> bool:
    return bool(tiers) and all(set(t) == {"pallas"} for t in tiers.values())


def oracle_predict(dep, feats, fused: bool = False):
    """The artifact's predictions through the oracle (``ref.py``) path:
    the same operands, every kernel dispatched with ``use_kernel=False``."""
    import jax.numpy as jnp

    from repro.core import encoding
    from repro.kernels import ops

    cc = dep.centroid_class
    if fused:
        return ops.predict_from_features(
            feats, dep.enc_params["projection"], dep.am_packed_t, cc,
            use_kernel=False)
    q = encoding.encode_query(dep.enc_params, dep.enc_cfg, feats)
    if dep.backend == "packed":
        return ops.predict_packed(q, dep.am_packed_t, cc,
                                  n_dims=dep.am_cfg.dim, use_kernel=False)
    if dep.backend == "unpacked":
        return ops.predict_classes(q, dep.am_binary, cc, use_kernel=False)
    if dep.backend == "imc":
        return ops.predict_imc(q, dep.am_analog, cc, sim=dep.sim,
                               offsets=dep.tile_offsets, use_kernel=False)
    if dep.backend == "multibit":
        return ops.predict_multibit(q, dep.am_planes_t, cc, sim=dep.sim,
                                    offsets=dep.tile_offsets,
                                    use_kernel=False)
    if dep.backend == "hierarchical":
        qp = ops.pack_rows(q, use_kernel=False)
        short, _ = ops.am_shortlist(qp, dep.super_packed_t,
                                    n_dims=dep.am_cfg.dim,
                                    s=dep.shortlist, use_kernel=False)
        idx, _ = ops.am_search_sparse(
            qp, dep.am_slab_t, dep.col_ids, short, dep.tile_start,
            dep.tile_count, n_dims=dep.am_cfg.dim, k=1,
            max_tiles=dep.max_tiles, use_kernel=False)
        return cc[jnp.maximum(idx[:, 0], 0)]
    raise ValueError(f"no oracle for backend {dep.backend!r}")


def serve_and_check(dep, reqs, *, fused: bool = False) -> dict:
    """Serve ``reqs`` (warmup pass, then a steady pass that must compile
    nothing) and compare every response with the oracle path."""
    import jax
    import numpy as np

    from repro import obs
    from repro.kernels import ops
    from repro.launch.serve_memhd import (
        build_report, metrics_summary, serve_batches,
    )

    before = ops.dispatch_breakdown()
    serve_batches(dep, reqs, MAX_BATCH, fused=fused, depth=DEPTH)
    with obs.count_compiles() as steady:
        t0 = time.perf_counter()
        responses, stats = serve_batches(dep, reqs, MAX_BATCH,
                                         warmup=False, fused=fused,
                                         depth=DEPTH)
        wall = time.perf_counter() - t0
    served_tiers = tier_delta(before, ops.dispatch_breakdown())
    report = build_report(dep, reqs, stats, wall, fused=fused,
                          metrics=metrics_summary(
                              recompiles_steady_state=steady()))

    feats = np.concatenate([r.feats for r in reqs])
    got = np.concatenate([responses[r.rid] for r in reqs])
    oracle = jax.jit(oracle_predict, static_argnames="fused")
    want = np.concatenate([
        np.asarray(oracle(dep, feats[i:i + MAX_BATCH], fused=fused))
        for i in range(0, len(feats), MAX_BATCH)])
    mismatches = int((got != want).sum())
    out = {
        "backend": report["backend"], "mode": report["mode"],
        "pipeline": report["pipeline"], "geometry": report["geometry"],
        "device": report["device"], "requests": report["requests"],
        "rows": report["rows"], "batches": report["batches"],
        "bit_exact": mismatches == 0, "mismatches": mismatches,
        "dispatch_tiers": served_tiers,
        "recompiles_steady_state":
            report["metrics"]["recompiles_steady_state"],
    }
    check(out["bit_exact"],
          f"{out['backend']}/{out['mode']}/{out['pipeline']} at "
          f"{out['geometry']}: {mismatches} predictions differ from "
          "the oracle")
    check(only_pallas(served_tiers),
          f"{out['backend']}: served kernels not all Pallas: "
          f"{served_tiers}")
    check(out["recompiles_steady_state"] == 0,
          f"{out['backend']}: steady-state pass compiled "
          f"{out['recompiles_steady_state']} programs")
    return out


def load(dataset: str, seed: int):
    from repro.data import load_dataset
    return load_dataset(dataset, seed=seed)


def requests_for(ds, seed: int):
    import numpy as np

    from repro.launch.serve_memhd import synthetic_requests
    return synthetic_requests(np.asarray(ds.test_x), N_REQUESTS, MAX_ROWS,
                              seed=seed)


# -- phases -------------------------------------------------------------------

def phase_train(seed: int):
    """Fit at 1024x1024 on the XLA path and through the kernel."""
    import jax
    import numpy as np

    from repro.configs.memhd_paper import paper_config
    from repro.core import MemhdModel
    from repro.kernels import ops
    from repro.obs import device_info

    t0 = time.perf_counter()
    ds = load("mnist", seed)
    enc, amc = paper_config("mnist", "1024x1024", epochs=TRAIN_EPOCHS)
    model = MemhdModel.create(jax.random.key(seed), enc, amc)
    fit_key = jax.random.key(seed + 1)
    m_xla, h_xla = model.fit(fit_key, ds.train_x, ds.train_y)
    before = ops.dispatch_breakdown()
    m_ker, h_ker = model.fit(fit_key, ds.train_x, ds.train_y,
                             use_kernel=True)
    tiers = tier_delta(before, ops.dispatch_breakdown())

    fp_x = np.asarray(m_xla.am_state["fp"])
    fp_k = np.asarray(m_ker.am_state["fp"])
    binary_equal = bool(np.array_equal(
        np.asarray(m_xla.am_state["binary"]),
        np.asarray(m_ker.am_state["binary"])))
    fp_close = bool(np.allclose(fp_k, fp_x, rtol=1e-5, atol=1e-5))
    miss_x = [r["train_miss"] for r in h_xla["curve"] if "train_miss" in r]
    miss_k = [r["train_miss"] for r in h_ker["curve"] if "train_miss" in r]
    miss_equal = all(abs(a - b) < 1e-6 for a, b in zip(miss_x, miss_k))
    emit("train", geometry=f"{amc.dim}x{amc.columns}",
         samples=int(ds.train_x.shape[0]), epochs=TRAIN_EPOCHS,
         device=device_info(), binary_equal=binary_equal,
         fp_max_abs_diff=float(np.abs(fp_k - fp_x).max()),
         fp_close=fp_close, train_miss_xla=miss_x,
         train_miss_kernel=miss_k,
         test_acc=float(m_xla.score(ds.test_x, ds.test_y)),
         dispatch_tiers=tiers, phase_s=round(time.perf_counter() - t0, 1))
    check(binary_equal, "train: kernel and XLA binary AMs differ")
    check(fp_close, "train: kernel and XLA float AMs differ past 1e-5")
    check(miss_equal, f"train: miss rates differ {miss_x} vs {miss_k}")
    check(set(tiers) == {"qail_update"} and only_pallas(tiers),
          f"train: kernel fit did not run the Pallas qail_update: {tiers}")
    return m_xla, ds


def serve_configs():
    """(label, deploy kwargs, fused) for every backend served."""
    from repro.core.types import ImcSimConfig

    out = []
    for mode in ("popcount", "unpack"):
        for fused in (False, True):
            out.append((dict(target="packed", mode=mode), fused))
    out += [
        (dict(target="unpacked"), False),
        (dict(target="imc", sim=ImcSimConfig(adc_bits=8)), False),
        (dict(target="multibit", cell_bits=2), False),
        (dict(target="multibit", cell_bits=4), False),
        (dict(target="hierarchical"), False),
    ]
    return out


def phase_serve(model, ds, seed: int) -> None:
    reqs = requests_for(ds, seed)
    for opts, fused in serve_configs():
        t0 = time.perf_counter()
        dep = model.deploy(**opts)
        out = serve_and_check(dep, reqs, fused=fused)
        emit("serve", **out, phase_s=round(time.perf_counter() - t0, 1))


def train_isolet(seed: int):
    """The ISOLET flagship (512x128), trained briefly on the XLA path."""
    import jax

    from repro.configs.memhd_paper import paper_config
    from repro.core import MemhdModel

    ds = load("isolet", seed)
    enc, amc = paper_config("isolet", epochs=TRAIN_EPOCHS)
    model = MemhdModel.create(jax.random.key(seed), enc, amc)
    model, _ = model.fit(jax.random.key(seed + 1), ds.train_x, ds.train_y)
    return model, ds


def planted_model(seed: int):
    """A 1024 x 100k model whose AM carries planted cluster structure,
    one class per centroid."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.hierarchical_search import D, planted_am
    from repro.core import EncoderConfig, MemhdConfig, MemhdModel
    from repro.core import am as am_lib

    rng = np.random.default_rng(seed)
    am, _ = planted_am(rng, HIER_C, HIER_G_PLANT)
    enc = EncoderConfig(kind="projection", features=784, dim=D)
    amc = MemhdConfig(dim=D, columns=HIER_C, classes=HIER_C)
    model = MemhdModel.create(jax.random.key(seed), enc, amc)
    state = am_lib.make_am_state(jnp.asarray(am, jnp.float32),
                                 jnp.arange(HIER_C, dtype=jnp.int32))
    check(bool(jnp.array_equal(state["binary"], jnp.asarray(am, jnp.float32))),
          "hierarchical: binarizing the planted AM changed it")
    return dataclasses.replace(model, am_state=state), am, rng


def phase_hierarchical(mnist, seed: int) -> None:
    import jax
    import numpy as np

    from benchmarks.hierarchical_search import exact_best_sims
    from repro.core import am as am_lib
    from repro.kernels import ops

    t0 = time.perf_counter()
    model, am, rng = planted_model(seed)
    dep = model.deploy(target="hierarchical", groups=HIER_G,
                       shortlist=HIER_S)
    served = serve_and_check(dep, requests_for(mnist, seed))

    # S = G searches every cluster: bit-exact with the flat packed scan.
    src = rng.integers(0, HIER_C, size=ANCHOR_QUERIES)
    flips = rng.random((ANCHOR_QUERIES, am.shape[1])) < 0.10
    q = np.where(flips, -am[src], am[src]).astype(np.float32)
    anchor = dataclasses.replace(dep, shortlist=dep.groups)
    before = ops.dispatch_breakdown()
    a_idx, a_sim = jax.tree.map(np.asarray, anchor.search_query(q, k=1))
    f_idx, f_sim = jax.tree.map(np.asarray, ops.am_search_packed(
        ops.pack_rows(q), am_lib.pack_am(model.am_state["binary"]),
        n_dims=model.am_cfg.dim))
    anchor_tiers = tier_delta(before, ops.dispatch_breakdown())
    anchor_exact = bool(np.array_equal(a_idx[:, 0], f_idx)
                        and np.array_equal(a_sim[:, 0], f_sim))
    # Recall@1 of the S-cluster shortlist on the same noisy queries
    # (tie-robust: the returned similarity equals the exact maximum).
    _, s_sim = jax.tree.map(np.asarray, dep.search_query(q, k=1))
    recall = float(np.mean(s_sim[:, 0] == exact_best_sims(q, am)))
    emit("hierarchical_at_scale", **served, columns=HIER_C,
         groups=dep.groups, shortlist=dep.shortlist,
         max_tiles=dep.max_tiles, anchor_queries=ANCHOR_QUERIES,
         anchor_bit_exact=anchor_exact, anchor_tiers=anchor_tiers,
         recall_at_1_s=recall,
         phase_s=round(time.perf_counter() - t0, 1))
    check(anchor_exact, "hierarchical: S = G differs from the flat scan")
    check(only_pallas(anchor_tiers),
          f"hierarchical anchor: not all Pallas: {anchor_tiers}")


def phase_online(seed: int) -> None:
    from repro.kernels import ops
    from repro.launch import serve_online
    from repro.obs import device_info

    t0 = time.perf_counter()
    before = ops.dispatch_breakdown()
    with contextlib.redirect_stdout(io.StringIO()):
        rep = serve_online.main(["--smoke", "--append-class",
                                 "--seed", str(seed)])
    tiers = tier_delta(before, ops.dispatch_breakdown())
    shape_stable = [g["shape_stable"] for g in rep["generations"]]
    emit("online", backend=rep["backend"], geometry=rep["geometry"],
         device=device_info(),
         model_generation=rep["model_generation"],
         generations_shape_stable=shape_stable,
         recompiles_steady_state=rep["recompiles_steady_state"],
         phase_accuracy={k: v.get("accuracy")
                         for k, v in rep["phases"].items()},
         dispatch_tiers=tiers, phase_s=round(time.perf_counter() - t0, 1))
    check(shape_stable == [True, False],
          f"online: generations shape_stable {shape_stable}")
    check(rep["recompiles_steady_state"] == 0,
          f"online: {rep['recompiles_steady_state']} steady recompiles")
    served = {k: v for k, v in tiers.items()
              if k in ("pack_rows", "am_search_packed")}
    check(only_pallas(served), f"online: served kernels {tiers}")


def phase_four_chips(seed: int) -> None:
    """ShardedArtifact over four chips vs the one-chip artifact."""
    import numpy as np

    from repro.deploy import ShardedArtifact
    from repro.deploy.padding import round_up
    from repro.launch.serve_memhd import serve_batches
    from repro.obs import device_info

    mnist = load("mnist", seed)
    model, _, _ = planted_model(seed)
    reqs = requests_for(mnist, seed)
    for opts in (dict(target="packed"),
                 dict(target="hierarchical", groups=HIER_G,
                      shortlist=HIER_S)):
        t0 = time.perf_counter()
        dep = model.deploy(**opts)
        one, _ = serve_batches(dep, reqs, MAX_BATCH, depth=DEPTH)
        sharded = ShardedArtifact(dep, devices=4)
        four, _ = serve_batches(sharded, reqs, MAX_BATCH, depth=DEPTH)
        mismatches = int(sum((one[r.rid] != four[r.rid]).sum()
                             for r in reqs))
        fn = sharded._sharded_fn("predict",
                                 sharded._method_local("predict"))
        rows = np.concatenate([r.feats for r in reqs])[:MAX_BATCH]
        rows = rows[:round_up(len(rows), 4)]
        out = fn(sharded.artifact, rows)
        shard_devices = sorted({s.device.id for s in out.addressable_shards})
        hlo = fn.lower(sharded.artifact, rows).compile().as_text().lower()
        found = [c for c in COLLECTIVES if c in hlo]
        emit("four_chips", backend=dep.backend, mode=dep.serving_mode,
             geometry=f"{dep.am_cfg.dim}x{dep.am_cfg.columns}",
             device=device_info(), requests=len(reqs),
             bit_exact=mismatches == 0, mismatches=mismatches,
             output_shard_devices=shard_devices, collectives=found,
             phase_s=round(time.perf_counter() - t0, 1))
        check(mismatches == 0, f"four chips: {dep.backend} differs from "
                               "one chip")
        check(len(shard_devices) == 4,
              f"four chips: output shards on devices {shard_devices}")
        check(not found, f"four chips: collectives {found}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip sharded check")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    try:
        from repro import compile_cache, obs
    except ImportError as e:
        print(f"chip_smoke: the repro package is missing next to this "
              f"script: {e}", file=sys.stderr)
        return 2
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform}); this "
              "check runs only on the chip", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 1
    compile_cache.enable()
    obs.install()

    try:
        if args.chips == 4:
            phase_four_chips(args.seed)
        else:
            model, mnist = phase_train(args.seed)
            phase_serve(model, mnist, args.seed)
            isolet_model, isolet = train_isolet(args.seed)
            phase_serve(isolet_model, isolet, args.seed)
            phase_hierarchical(mnist, args.seed)
            phase_online(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device_fields()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
