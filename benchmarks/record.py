"""Persistent benchmark recording: one ``BENCH_<name>.json`` per bench.

Before this sink existed every bench printed CSV to stdout and the
numbers evaporated with the terminal — five PRs of kernel and serving
work with no recorded perf trajectory. Now ``benchmarks.run`` opens a
recorder around each bench module, every ``common.row(...)`` call is
mirrored into it as a structured metric (``common.time_fn`` attaches
its full sample statistics — min/p50/p95/p99 — to the matching row),
and the finished record is written as a schema-versioned JSON artifact:

    benchmarks/results/BENCH_<name>.json      (override: $MEMHD_BENCH_DIR
                                               or run.py --record-dir)

``benchmarks.gate`` diffs these against the committed baselines in
``benchmarks/baselines/`` and fails CI on slowdowns or missing metrics;
``launch/serve_memhd.py --record-dir`` routes its serving report
through ``from_report`` so QPS/latency land in the same trajectory.

Schema (v1) — the top-level key set and the per-metric required keys
are FROZEN (tests/test_bench_harness.py); extend by adding optional
per-metric keys or bumping ``SCHEMA_VERSION``:

    {
      "schema_version": 1,
      "bench": "<name>",               # BENCH_<name>.json
      "created_unix": 1733...,
      "git_sha": "abc1234" | null,
      "jax_backend": "cpu" | "tpu" | ...,
      "device": {"platform": "tpu", "device_kind": "TPU v5 lite",
                 "count": 1},
      "jax_version": "0.4...",
      "meta": {...},                   # geometry / workload metadata
      "metrics": {
        "<row name>": {
          "us_per_call": 12.5,         # required
          "derived": "...",            # required (stringified)
          # attached when the row came from a time_fn measurement:
          "min_us": ..., "p50_us": ..., "p95_us": ..., "p99_us": ...,
          "mean_us": ..., "n_samples": 5, "samples_us": [...],
          # plus any structured extras the bench passed to row(**extra)
        }, ...
      }
    }
"""
from __future__ import annotations

import json
import math
import os
import statistics
import subprocess
import time
from typing import Dict, List, Optional

SCHEMA_VERSION = 1
RECORD_PREFIX = "BENCH_"
ENV_DIR = "MEMHD_BENCH_DIR"

# The frozen schema: tests/test_bench_harness.py asserts these exactly.
TOP_LEVEL_KEYS = frozenset({
    "schema_version", "bench", "created_unix", "git_sha",
    "jax_backend", "device", "jax_version", "meta", "metrics",
})
METRIC_REQUIRED_KEYS = frozenset({"us_per_call", "derived"})
TIMING_KEYS = frozenset({
    "min_us", "p50_us", "p95_us", "p99_us", "mean_us", "n_samples",
    "samples_us",
})

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_ACTIVE: Optional["Recorder"] = None


def _dispatch_breakdown() -> Optional[Dict[str, Dict[str, int]]]:
    """{kernel: {tier: count}} from the obs registry (None if repro
    isn't importable — record.py must stay usable standalone)."""
    try:
        from repro.kernels import ops
    except ImportError:
        return None
    return ops.dispatch_breakdown()


def _obs_meta(baseline: Optional[Dict[str, Dict[str, int]]]) -> Optional[Dict]:
    """The record's ``meta["obs"]`` block: XLA compile count plus the
    dispatch-tier counts THIS bench added over ``baseline`` (the
    process-wide registry accumulates across benches in one run.py
    process, so the per-bench delta is what's attributable). The gate
    reads ``dispatch_tiers`` to flag a kernel silently falling off its
    fast path even when timings stay inside the noise floor."""
    current = _dispatch_breakdown()
    if current is None:
        return None
    tiers: Dict[str, Dict[str, int]] = {}
    base = baseline or {}
    for kernel, by_tier in current.items():
        for tier, n in by_tier.items():
            delta = n - base.get(kernel, {}).get(tier, 0)
            if delta > 0:
                tiers.setdefault(kernel, {})[tier] = delta
    try:
        from repro.obs import jaxmon
        compiles = jaxmon.compiles()
    except ImportError:
        compiles = 0
    return {"compiles_total": compiles, "dispatch_tiers": tiers}


def results_dir() -> str:
    """Default artifact directory (gitignored; $MEMHD_BENCH_DIR wins)."""
    return os.environ.get(ENV_DIR) or os.path.join(
        _REPO_ROOT, "benchmarks", "results")


def baselines_dir() -> str:
    """The committed per-PR baseline set the regression gate diffs against."""
    return os.path.join(_REPO_ROOT, "benchmarks", "baselines")


def git_sha() -> Optional[str]:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=_REPO_ROOT,
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or None
    except OSError:
        return None


def timing_stats(samples_s: List[float]) -> Dict[str, object]:
    """Full sample statistics for one timed call, in microseconds.

    ``p50_us`` is the TRUE median (``statistics.median`` — the old
    ``sorted[n // 2]`` was the upper-middle element for even n); the
    min rides along so single-sample jitter on a shared 1-core CI
    container is visible next to the central tendency. p95/p99 use the
    nearest-rank definition (== max for the usual 3-5 samples, still
    meaningful once a bench passes more iters).
    """
    if not samples_s:
        raise ValueError("timing_stats needs at least one sample")
    us = sorted(s * 1e6 for s in samples_s)

    def rank(p: float) -> float:
        return us[min(len(us) - 1, max(0, math.ceil(p / 100 * len(us)) - 1))]

    return {
        "min_us": us[0],
        "p50_us": float(statistics.median(us)),
        "p95_us": rank(95),
        "p99_us": rank(99),
        "mean_us": float(statistics.fmean(us)),
        "n_samples": len(us),
        "samples_us": [round(u, 3) for u in us],
    }


class Recorder:
    """Accumulates one bench run's structured metrics into a record."""

    def __init__(self, bench: str, out_dir: Optional[str] = None,
                 meta: Optional[Dict] = None):
        self.bench = bench
        self.out_dir = out_dir or results_dir()
        self.meta: Dict = dict(meta or {})
        self.metrics: Dict[str, Dict] = {}
        # Count XLA compiles from here on (idempotent; no-op when the
        # repro package isn't importable) and remember the dispatch
        # counters' state so record() can attribute this bench's delta.
        try:
            from repro.obs import jaxmon
            jaxmon.install()
        except ImportError:
            pass
        self._obs_baseline = _dispatch_breakdown()
        # Pending time_fn stats, keyed by their exact median float: the
        # next row() whose us_per_call is that median claims them, so
        # every timed row carries min/p50/p95/p99 with zero changes in
        # the bench modules.
        self._pending: Dict[float, Dict] = {}

    def note_timing(self, stats: Dict) -> None:
        if len(self._pending) > 64:  # unclaimed stats: drop the backlog
            self._pending.clear()
        self._pending[float(stats["p50_us"])] = stats

    def emit(self, name: str, us_per_call: float, derived,
             **extra) -> None:
        metric: Dict[str, object] = {
            "us_per_call": float(us_per_call),
            "derived": str(derived),
        }
        stats = self._pending.pop(float(us_per_call), None)
        if stats is not None:
            metric.update(stats)
        metric.update(extra)
        self.metrics[name] = metric

    def record(self) -> Dict:
        import jax

        from repro.obs import device_info
        meta = dict(self.meta)
        obs_meta = _obs_meta(self._obs_baseline)
        if obs_meta is not None:
            meta["obs"] = obs_meta
        return {
            "schema_version": SCHEMA_VERSION,
            "bench": self.bench,
            "created_unix": int(time.time()),
            "git_sha": git_sha(),
            "jax_backend": jax.default_backend(),
            "device": device_info(),
            "jax_version": jax.__version__,
            "meta": meta,
            "metrics": self.metrics,
        }

    @property
    def path(self) -> str:
        return os.path.join(self.out_dir,
                            f"{RECORD_PREFIX}{self.bench}.json")

    def write(self) -> str:
        os.makedirs(self.out_dir, exist_ok=True)
        with open(self.path, "w") as f:
            json.dump(self.record(), f, indent=1)
            f.write("\n")
        return self.path


def start(bench: str, out_dir: Optional[str] = None,
          meta: Optional[Dict] = None) -> Recorder:
    """Open the process-wide active recorder (row()/time_fn feed it)."""
    global _ACTIVE
    _ACTIVE = Recorder(bench, out_dir=out_dir, meta=meta)
    return _ACTIVE


def active() -> Optional[Recorder]:
    return _ACTIVE


def finish(write: bool = True) -> Optional[str]:
    """Close the active recorder; returns the written path (or None)."""
    global _ACTIVE
    rec, _ACTIVE = _ACTIVE, None
    if rec is None or not write:
        return None
    return rec.write()


def emit_row(name: str, us_per_call: float, derived, **extra) -> None:
    """Structured mirror of ``common.row`` — no-op without a recorder."""
    if _ACTIVE is not None:
        _ACTIVE.emit(name, us_per_call, derived, **extra)


def note_timing(stats: Dict) -> None:
    if _ACTIVE is not None:
        _ACTIVE.note_timing(stats)


def from_report(bench: str, report: Dict, out_dir: Optional[str] = None,
                ) -> str:
    """Wrap a flat JSON report (e.g. serve_memhd's) into a BENCH record.

    Numeric scalar fields become metrics (``value`` carries the number;
    ``lat_ms_*`` fields additionally populate ``us_per_call`` so the
    regression gate treats them as lower-is-better timings); everything
    else lands in ``meta``. Writes immediately, independent of the
    active recorder.
    """
    rec = Recorder(bench, out_dir=out_dir)
    for key, val in report.items():
        if isinstance(val, bool) or not isinstance(val, (int, float)):
            rec.meta[key] = val
            continue
        us = float(val) * 1e3 if key.startswith("lat_ms") else 0.0
        rec.emit(key, us, val, value=float(val))
    return rec.write()
