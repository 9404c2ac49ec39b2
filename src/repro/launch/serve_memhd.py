"""Batched MEMHD serving driver: the packed-AM classification workload.

``launch/serve.py`` serves LM decode; this driver serves the paper's
actual deployment scenario — a stream of classification requests of raw
feature rows against the resident AM of ANY registered deployment
backend (``--target packed | unpacked | imc | hierarchical |
multibit``). Requests of ragged
sizes are greedily packed into batches (a request never splits), each
batch is zero-padded up to the next tile multiple so every launch hits
the same compiled kernel shapes, and batches are served through a
double-buffered pipeline: the host prepares/pads batch k+1 while batch
k is in flight on the device (``--depth`` controls how many batches may
be in flight; 1 recovers the fully synchronous loop).

``--devices N`` shards every batch over a data-parallel mesh of the
first N local devices (``repro.deploy.ShardedArtifact``: AM replicated,
batch rows sharded) — bit-exact with single-device serving. ``--fused``
serves each batch through ``predict_features`` — the single-dispatch
chain of the fused encode/sign/bitpack kernel into the packed search
(no float hypervector in HBM); the default serves the staged
encode -> binarize -> pack -> search path. Predictions are bit-exact
between the two modes.

The report mirrors serve.py's JSON contract: wall time, per-batch
latency percentiles, queries/s, per-device throughput, plus the
backend label and residence accounting of the served artifact.

Usage:
  PYTHONPATH=src python -m repro.launch.serve_memhd --smoke --fused \
      --requests 64 --max-batch 256
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m repro.launch.serve_memhd --smoke --devices 8
"""
from __future__ import annotations

import argparse
import dataclasses
import itertools
import json
import logging
import math
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro import compile_cache, obs

# Shared tile-padding helpers (re-exported here for existing callers).
from repro.deploy.padding import pad_to_multiple, round_up  # noqa: F401
from repro.obs import span

log = logging.getLogger("serve_memhd")

TILE_B = 8  # batch padding granularity (float32 sublane tile)

# Batch sequence numbers, unique over the process's serve_batches calls:
# every span of a batch carries its number as ``batch=``.
_BATCH_IDS = itertools.count()


@dataclasses.dataclass(frozen=True)
class Request:
    """One classification request: a block of feature rows."""

    rid: int
    feats: np.ndarray  # (n, f)

    @property
    def size(self) -> int:
        return self.feats.shape[0]


def make_batches(requests: Sequence[Request], max_batch: int,
                 ) -> List[List[Request]]:
    """Greedy first-fit batching: fill up to ``max_batch`` rows per batch.

    Requests are taken in arrival order and never split; a request larger
    than ``max_batch`` gets a batch of its own (it still pads to a tile
    multiple, it just can't share).
    """
    batches: List[List[Request]] = []
    cur: List[Request] = []
    cur_rows = 0
    for req in requests:
        if cur and cur_rows + req.size > max_batch:
            batches.append(cur)
            cur, cur_rows = [], 0
        cur.append(req)
        cur_rows += req.size
    if cur:
        batches.append(cur)
    return batches


def serve_batches(deployed, requests: Sequence[Request],
                  max_batch: int = 256, tile: int = TILE_B,
                  warmup: bool = True, fused: bool = False,
                  depth: int = 1, topk: int = 0,
                  ) -> Tuple[Dict[int, np.ndarray], Dict]:
    """Run the request stream through the deployed model.

    ``warmup=True`` pre-compiles every distinct padded batch shape the
    stream will hit (tile padding keeps that set small) so the reported
    latencies measure serving, not jit compilation. ``fused=True``
    serves each batch through ``predict_features`` (the single-dispatch
    fused pipeline) instead of the staged ``predict``; predictions are
    bit-exact between the two.

    ``depth`` is the double-buffer depth: up to ``depth`` batches may be
    in flight on the device while the host concatenates and pads the
    next one (jax dispatch is async; the host only blocks when the
    pipeline is full). The default ``depth=1`` is the synchronous loop.

    Latency is reported DECOMPOSED, at any depth: ``lat_ms_*`` is the
    total dispatch -> result-ready time per batch, split into
    ``queue_ms_*`` (time the batch spent waiting behind earlier
    in-flight batches — the pipeline queue wait that used to be
    silently folded into ``lat_ms_*`` whenever ``depth > 1``) and
    ``service_ms_*`` (the batch's own device time once the queue ahead
    of it drained). Per batch ``queue + service == lat`` exactly; at
    ``depth=1`` queue wait is identically zero. The decomposition
    assumes in-order device execution (one stream), which is how a jax
    device dispatch queue drains.

    An empty request stream reports ``batches: 0`` and ``None`` for
    every latency field (JSON ``null``) — no fabricated zero rows.

    Each batch also emits host spans (``host_prep`` / ``pad`` /
    ``dispatch`` / ``device_wait``), all carrying the batch's sequence
    number as ``batch=``, and the call a ``stats`` span around its
    summary; all are exportable as a Chrome trace via ``repro.obs``.
    The call feeds the ``serve_batch_ms`` histogram /
    ``serve_rows_total`` counters of the default metrics registry.

    ``topk >= 1`` serves through the backend's ``predict_topk`` — the
    fused streaming top-k kernel epilogue — and each response row widens
    to the request's k best classes.

    Returns (responses, stats): responses maps rid -> (n,) predicted
    classes ((n, topk) when ``topk >= 1``); stats holds per-batch
    latencies and padding accounting.
    """
    if depth < 1:
        raise ValueError(f"depth must be >= 1, got {depth}")
    if topk and fused:
        raise ValueError("topk serving and the fused feature pipeline "
                         "are mutually exclusive")
    # Sharded artifacts need every batch to split evenly across devices.
    tile = math.lcm(tile, getattr(deployed, "row_multiple", 1))
    if topk:
        # (B, k) classes out of the streaming top-k epilogue; the ids
        # and sims of the triple stay available via predict_topk itself.
        predict = lambda x: deployed.predict_topk(x, topk)[0]  # noqa: E731
    else:
        predict = (deployed.predict_features if fused
                   else deployed.predict)
    batches = make_batches(requests, max_batch)
    if warmup and requests:
        # The warmup batches must hit the SAME jit signatures the stream
        # will: shape AND dtype (a non-f32 stream warmed with f32 zeros
        # would silently recompile every steady-state shape).
        n_feats = requests[0].feats.shape[1]
        dtype = requests[0].feats.dtype
        shapes = {round_up(sum(r.size for r in b), tile) for b in batches}
        for rows in sorted(shapes):
            jax.block_until_ready(predict(
                np.zeros((rows, n_feats), dtype)))
    responses: Dict[int, np.ndarray] = {}
    lat_ms: List[float] = []
    queue_ms: List[float] = []
    service_ms: List[float] = []
    rows_real = rows_padded = 0
    inflight: deque = deque()  # (seq, batch, n_valid, result, t_disp)
    last_ready = [float("-inf")]  # when the device finished batch k-1
    hist = obs.histogram(
        "serve_batch_ms", "per-batch serving latency by stage")
    served_rows = obs.counter("serve_rows_total",
                              "feature rows served (pre-padding)")
    served_reqs = obs.counter("serve_requests_total",
                              "classification requests served")

    def _drain_one():
        seq, batch, n_valid, fut, t_disp = inflight.popleft()
        with span("device_wait", batch=seq):
            jax.block_until_ready(fut)
        t_ready = time.perf_counter()
        # The batch could only start once everything dispatched before
        # it had drained (in-order device queue): time up to the
        # previous batch's completion is queue wait, the rest is this
        # batch's own service time.
        lat = t_ready - t_disp
        queue = min(lat, max(0.0, last_ready[0] - t_disp))
        last_ready[0] = t_ready
        lat_ms.append(lat * 1e3)
        queue_ms.append(queue * 1e3)
        service_ms.append((lat - queue) * 1e3)
        hist.observe(lat * 1e3, stage="total")
        hist.observe(queue * 1e3, stage="queue")
        hist.observe((lat - queue) * 1e3, stage="service")
        pred = np.asarray(fut)[:n_valid]
        ofs = 0
        for r in batch:
            responses[r.rid] = pred[ofs:ofs + r.size]
            ofs += r.size

    for batch in batches:
        seq = next(_BATCH_IDS)
        # Host-side prep of batch k+1 overlaps device work on batch k.
        with span("host_prep", batch=seq, requests=len(batch)):
            feats = np.concatenate([r.feats for r in batch])
            with span("pad", batch=seq):
                padded, n_valid = pad_to_multiple(feats, tile)
        rows_real += n_valid
        rows_padded += padded.shape[0]
        t0 = time.perf_counter()
        with span("dispatch", batch=seq, rows=padded.shape[0]):
            fut = predict(padded)
        inflight.append((seq, batch, n_valid, fut, t0))
        while len(inflight) >= depth:
            _drain_one()
    while inflight:
        _drain_one()
    # The call's summary is host work between its last batch and the
    # caller's next call, while the device waits.
    with span("stats", batches=len(batches)):
        served_rows.inc(rows_real)
        served_reqs.inc(len(requests))
        stats = {
            "depth": depth,
            "batches": len(batches),
            "rows_real": rows_real,
            "rows_padded": rows_padded,
            "pad_overhead": (round(rows_padded / rows_real - 1, 3)
                             if rows_real else None),
            **_lat_fields("lat_ms", lat_ms),
            **_lat_fields("service_ms", service_ms),
            **_lat_fields("queue_ms", queue_ms),
        }
    return responses, stats


def _lat_fields(prefix: str, vals: List[float],
                ) -> Dict[str, Optional[float]]:
    """min/p50/p95/p99/total fields for one latency series; all None
    (JSON null) when the stream produced no batches."""
    if not vals:
        return {f"{prefix}_{s}": None
                for s in ("min", "p50", "p95", "p99", "total")}
    a = np.asarray(vals)
    return {
        f"{prefix}_min": round(float(a.min()), 3),
        f"{prefix}_p50": round(float(np.percentile(a, 50)), 3),
        f"{prefix}_p95": round(float(np.percentile(a, 95)), 3),
        f"{prefix}_p99": round(float(np.percentile(a, 99)), 3),
        f"{prefix}_total": round(float(a.sum()), 3),
    }


def synthetic_requests(feats: np.ndarray, n_requests: int,
                       max_size: int, seed: int = 0) -> List[Request]:
    """Ragged request stream sampled from a feature pool."""
    rng = np.random.default_rng(seed)
    reqs = []
    for rid in range(n_requests):
        n = int(rng.integers(1, max_size + 1))
        rows = rng.integers(0, feats.shape[0], size=n)
        reqs.append(Request(rid=rid, feats=feats[rows]))
    return reqs


def metrics_summary(recompiles_steady_state: Optional[int] = None,
                    ) -> Dict:
    """The report's ``metrics`` section: runtime facts wall clocks
    can't show — total XLA compiles, compiles observed in the
    steady-state (post-warmup) serving window, and the per-kernel
    dispatch-tier breakdown (which execution tier actually served each
    kernel — a silent fallback to the oracle path is visible here)."""
    from repro.kernels import ops
    out = {
        "compiles_total": obs.jaxmon.compiles(),
        "dispatch_tiers": ops.dispatch_breakdown(),
    }
    if recompiles_steady_state is not None:
        out["recompiles_steady_state"] = int(recompiles_steady_state)
    return out


def build_report(deployed, requests: Sequence[Request], stats: Dict,
                 wall_s: float, fused: bool = False, topk: int = 0,
                 metrics: Optional[Dict] = None) -> Dict:
    """Assemble the serving JSON report — the driver's output contract.

    Key set and value types are stable (asserted in
    tests/test_serving.py); downstream dashboards parse this. Works for
    any ``DeployedArtifact`` backend (and its sharded wrapper): the
    ``backend`` / ``devices`` fields make reports from different
    substrates and device counts comparable. ``metrics`` is the
    runtime-introspection section (``metrics_summary()``); it defaults
    to a fresh summary with no steady-state window. ``device`` names
    what served the stream (``obs.device_info``).
    """
    n_rows = sum(r.size for r in requests)
    devices = int(getattr(deployed, "n_devices", 1))
    rows_per_s = round(n_rows / wall_s, 1) if wall_s else 0.0
    return {
        "workload": "memhd_classify",
        "backend": deployed.backend,
        "device": obs.device_info(),
        "devices": devices,
        "packed": bool(getattr(deployed, "packed", False)),
        "mode": deployed.serving_mode,
        "pipeline": "fused" if fused else "staged",
        "topk": int(topk),  # 0 = argmax serving; k >= 1 = top-k epilogue
        "geometry": f"{deployed.am_cfg.dim}x{deployed.am_cfg.columns}",
        "requests": len(requests),
        "rows": n_rows,
        "wall_s": round(wall_s, 3),
        "qps": round(len(requests) / wall_s, 1) if wall_s else 0.0,
        "rows_per_s": rows_per_s,
        "rows_per_s_per_device": round(rows_per_s / devices, 1),
        "resident_am_bytes": deployed.resident_am_bytes,
        "am_memory_ratio": round(deployed.am_memory_ratio, 2),
        "metrics": metrics if metrics is not None else metrics_summary(),
        **stats,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny training budget (CI-sized)")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--max-size", type=int, default=32,
                    help="max rows per request")
    ap.add_argument("--max-batch", type=int, default=256)
    ap.add_argument("--target", default=None,
                    choices=["packed", "unpacked", "imc", "hierarchical",
                             "multibit"],
                    help="deployment backend (registry target)")
    ap.add_argument("--cell-bits", type=int, default=4,
                    help="multibit: bits per resident AM cell (2-8)")
    ap.add_argument("--mode", default="popcount",
                    choices=["popcount", "unpack"])
    ap.add_argument("--topk", type=int, default=0,
                    help="serve k candidates per row through the fused "
                         "streaming top-k epilogue (hierarchical "
                         "backend); 0 = argmax serving")
    ap.add_argument("--groups", type=int, default=None,
                    help="hierarchical: G super-centroids "
                         "(default ~sqrt(C))")
    ap.add_argument("--shortlist", type=int, default=None,
                    help="hierarchical: S clusters searched per query "
                         "(default G — exact)")
    ap.add_argument("--unpacked", action="store_true",
                    help="legacy alias for --target unpacked")
    ap.add_argument("--fused", action="store_true",
                    help="serve raw features through the single-dispatch "
                         "fused encode->pack->search pipeline")
    ap.add_argument("--devices", type=int, default=1,
                    help="shard every batch over the first N local "
                         "devices (data-parallel serving)")
    ap.add_argument("--depth", type=int, default=2,
                    help="double-buffer depth (batches in flight)")
    ap.add_argument("--record-dir", default=None,
                    help="also persist the report as a schema-versioned "
                         "BENCH_serve_memhd.json (benchmarks.record) in "
                         "this directory — the perf-trajectory sink")
    ap.add_argument("--metrics-out", default=None,
                    help="write the full obs metrics-registry snapshot "
                         "(counters/gauges/histograms) as JSON here")
    ap.add_argument("--trace-out", default=None,
                    help="write the host-span Chrome trace-event JSON "
                         "here (open in Perfetto / chrome://tracing)")
    ap.add_argument("--log-json", action="store_true",
                    help="structured one-JSON-per-line logging")
    args = ap.parse_args()
    obs.setup_logging(json_mode=args.log_json)
    compile_cache.enable()
    obs.install()  # count XLA compiles from the very first trace

    if args.target and args.unpacked:
        ap.error("--unpacked is the legacy alias; drop it with --target")
    target = args.target or ("unpacked" if args.unpacked else "packed")
    if args.fused and target != "packed":
        ap.error("--fused needs the packed backend (--target packed)")
    if args.topk and target != "hierarchical":
        ap.error("--topk needs the top-k backend "
                 "(--target hierarchical)")
    if (args.groups or args.shortlist) and target != "hierarchical":
        ap.error("--groups/--shortlist only apply to "
                 "--target hierarchical")

    from repro.core import EncoderConfig, MemhdConfig, MemhdModel
    from repro.data import load_dataset
    from repro.deploy import ShardedArtifact

    per_class = 80 if args.smoke else 400
    epochs = 2 if args.smoke else 20
    ds = load_dataset("mnist", train_per_class=per_class,
                      test_per_class=40)
    enc = EncoderConfig(kind="projection", features=ds.features, dim=128)
    amc = MemhdConfig(dim=128, columns=128, classes=ds.classes,
                      epochs=epochs, kmeans_iters=5)
    model = MemhdModel.create(jax.random.key(0), enc, amc)
    model, _ = model.fit(jax.random.key(1), ds.train_x, ds.train_y)
    if target in ("packed", "unpacked"):
        deployed = model.deploy(target=target, mode=args.mode)
    elif target == "hierarchical":
        deployed = model.deploy(target=target, groups=args.groups,
                                shortlist=args.shortlist)
    elif target == "multibit":
        deployed = model.deploy(target=target, cell_bits=args.cell_bits)
    else:
        deployed = model.deploy(target=target)
    if args.devices > 1:
        deployed = ShardedArtifact(deployed, devices=args.devices)
        log.info("sharded serving over %d devices", args.devices)

    reqs = synthetic_requests(np.asarray(ds.test_x), args.requests,
                              args.max_size)
    # Warmup pass compiles every padded batch shape; the timed pass then
    # measures pure serving — and must not compile ANYTHING new
    # (``recompiles_steady_state`` in the report's metrics section
    # stays 0 unless the padding contract regressed).
    with span("warmup"):
        serve_batches(deployed, reqs, args.max_batch, fused=args.fused,
                      depth=args.depth, topk=args.topk)
    with obs.count_compiles() as steady_compiles:
        t0 = time.time()
        with span("serve", requests=len(reqs), depth=args.depth):
            responses, stats = serve_batches(
                deployed, reqs, args.max_batch, warmup=False,
                fused=args.fused, depth=args.depth, topk=args.topk)
        wall = time.time() - t0
    obs.update_memory_gauges()
    report = build_report(
        deployed, reqs, stats, wall, fused=args.fused, topk=args.topk,
        metrics=metrics_summary(
            recompiles_steady_state=steady_compiles()))
    print(json.dumps(report, indent=1))
    assert len(responses) == len(reqs)
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(obs.snapshot(), f, indent=1)
        log.info("metrics snapshot -> %s", args.metrics_out)
    if args.trace_out:
        obs.export_chrome_trace(args.trace_out)
        log.info("chrome trace -> %s", args.trace_out)
    if args.record_dir:
        # benchmarks/ lives at the repo root, not under src/ — recording
        # therefore needs the repo root on sys.path (python -m from the
        # checkout has it). Fail loudly, never silently skip the record.
        try:
            from benchmarks import record
        except ImportError as e:
            raise SystemExit(
                f"--record-dir needs the benchmarks package importable "
                f"(run from the repo root): {e}")
        path = record.from_report("serve_memhd", report,
                                  out_dir=args.record_dir)
        log.info("recorded -> %s", path)


if __name__ == "__main__":
    main()
