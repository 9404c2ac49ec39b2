"""JAX runtime introspection -> the obs metrics registry.

Three windows into the runtime the rest of the repo can't see from
wall clocks alone:

  * **Compile/recompile counting.** ``install()`` registers a
    ``jax.monitoring`` duration listener; every XLA backend compile
    increments ``jax_compiles_total`` (and feeds ``jax_compile_seconds``
    when its duration is finite and non-negative). A cached
    executable fires no event, so the counter's *delta* over a window
    is exactly the number of fresh compilations in that window — the
    basis of ``assert_no_recompiles`` and the serving driver's
    ``recompiles_steady_state`` report field (a steady-state serving
    loop that still compiles is mis-padded and will stutter under
    load).
  * **Device memory gauges.** ``update_memory_gauges()`` snapshots
    ``device.memory_stats()`` per device into
    ``jax_device_memory_bytes{device=..., stat=...}`` (CPU backends
    return None — skipped, not faked).
  * **Steady-state assertion helper.** ``assert_no_recompiles()`` is
    the context manager CI and tests wrap around a supposedly
    shape-stable region; it raises ``RecompileError`` with the compile
    delta when jit retraces inside.

``install()`` is idempotent and registers into the *default* registry;
``jax.monitoring`` has no per-listener removal (only a global clear),
so one process-lifetime registration is the contract.
"""
from __future__ import annotations

import math
import threading
from contextlib import contextmanager
from typing import Dict, Optional

from repro.obs import metrics as _metrics

# The duration event the XLA backend fires once per *actual* compile
# (cache hits are silent) — observed stable across jax 0.4.x.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_COMPILE_BUCKETS = _metrics.log_buckets(1e-3, 1e3, per_decade=3)

_install_lock = threading.Lock()
_installed = False


class RecompileError(AssertionError):
    """A region that must be shape-stable recompiled anyway."""


def _compile_instruments():
    return (_metrics.counter(
                "jax_compiles_total",
                "XLA backend compilations (cache hits fire no event)"),
            _metrics.histogram(
                "jax_compile_seconds", "XLA backend compile durations",
                buckets=_COMPILE_BUCKETS))


def _on_duration(name: str, dur: float, **kw) -> None:
    """The ``jax.monitoring`` duration listener: counts every backend
    compile, and times those whose duration is a finite non-negative
    number. JAX times compiles on the wall clock, which can step back,
    so a duration may read negative; a listener that raised would end
    the process, so this one never does."""
    if name != COMPILE_EVENT:
        return
    compiles, compile_secs = _compile_instruments()
    compiles.inc()
    if math.isfinite(dur) and dur >= 0:
        compile_secs.observe(dur)


def install() -> None:
    """Register the jax.monitoring compile listener (once per process)."""
    global _installed
    with _install_lock:
        if _installed:
            return
        from jax import monitoring

        _compile_instruments()
        monitoring.register_event_duration_secs_listener(_on_duration)
        _installed = True


def installed() -> bool:
    return _installed


def compiles() -> int:
    """Backend compiles observed since ``install()`` (0 before it)."""
    fam = _metrics.REGISTRY.get("jax_compiles_total")
    return int(fam.total()) if fam is not None else 0


@contextmanager
def count_compiles():
    """Yields a zero-arg callable returning the compile delta so far.

    Usable mid-region: ``with count_compiles() as n: ...; n()``.
    """
    install()
    before = compiles()
    yield lambda: compiles() - before


@contextmanager
def assert_no_recompiles(what: str = "steady-state region"):
    """Raise ``RecompileError`` if any XLA compile happens inside.

    Wrap the *post-warmup* body — the steady-state serving loop, the
    second epoch of a training run. A failure means some input shape or
    static argument escaped the padding contract.
    """
    install()
    before = compiles()
    yield
    delta = compiles() - before
    if delta:
        raise RecompileError(
            f"{what}: {delta} recompile(s) in a region that must be "
            f"shape-stable (jax_compiles_total {before} -> "
            f"{before + delta})")


def device_info() -> Dict[str, object]:
    """The devices this process runs on, as JAX reports them: the first
    device's platform and kind, and the device count. Every report the
    program emits names them, so a CPU number never passes for a chip
    number."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind, "count": len(devs)}


def update_memory_gauges() -> Dict[str, Dict[str, float]]:
    """Per-device ``memory_stats()`` -> gauges; returns what it set.

    Backends without allocator stats (CPU) yield no gauges — absent is
    honest, zero would be a lie.
    """
    import jax

    gauge = _metrics.gauge(
        "jax_device_memory_bytes",
        "per-device allocator stats from device.memory_stats()")
    out: Dict[str, Dict[str, float]] = {}
    for dev in jax.devices():
        stats: Optional[Dict] = None
        if hasattr(dev, "memory_stats"):
            try:
                stats = dev.memory_stats()
            except Exception:
                stats = None
        if not stats:
            continue
        label = f"{dev.platform}:{dev.id}"
        kept = {k: float(v) for k, v in stats.items()
                if isinstance(v, (int, float))}
        for stat, val in kept.items():
            gauge.set(val, device=label, stat=stat)
        out[label] = kept
    return out
