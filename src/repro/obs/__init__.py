"""repro.obs — the unified observability layer.

Three pillars, one import:

  * ``repro.obs.metrics`` — thread-safe process-local registry of
    counters / gauges / log-bucket histograms; ``snapshot()`` (stable
    JSON dict) and Prometheus text exposition.
  * ``repro.obs.trace`` — nested host spans (``with span("pad"):``)
    exported as Chrome trace-event JSON (Perfetto-viewable), with an
    optional ``jax.profiler.TraceAnnotation`` bridge.
  * ``repro.obs.jaxmon`` — JAX runtime introspection: jit
    compile/recompile counters via ``jax.monitoring``, per-device
    memory gauges, the ``assert_no_recompiles`` steady-state helper,
    and ``device_info`` (what every report names as its device).

Plus the shared driver plumbing: ``setup_logging`` (one consistent
format for every launch driver, ``--log-json`` structured option) and
``EventLog`` (append-only JSONL run-event streams).

``metrics``/``trace``/``logs`` are stdlib-only; only ``jaxmon``
touches jax, and only lazily (safe to import repro.obs anywhere).
"""
from repro.obs import jaxmon, metrics, trace
from repro.obs.jaxmon import (
    RecompileError, assert_no_recompiles, count_compiles, device_info,
    install, update_memory_gauges,
)
from repro.obs.logs import EventLog, setup_logging
from repro.obs.metrics import (
    REGISTRY, counter, gauge, histogram, log_buckets, render_prometheus,
    snapshot, timed_ms,
)
from repro.obs.trace import TRACER, export_chrome_trace, span

__all__ = [
    "metrics", "trace", "jaxmon",
    "REGISTRY", "counter", "gauge", "histogram", "log_buckets",
    "snapshot", "render_prometheus", "timed_ms",
    "TRACER", "span", "export_chrome_trace",
    "install", "count_compiles", "assert_no_recompiles",
    "RecompileError", "update_memory_gauges", "device_info",
    "setup_logging", "EventLog",
]
