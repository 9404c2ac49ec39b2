"""What every mode shares: the measured window, its trace, the device
memory peak and the program's spans.

A mode (``bench/modes/<kind>.py``) builds its system, warms up, and
then wraps its measured window in ``Window``. With ``--trace 1`` the
window runs under the JAX profiler with a ``bench.window`` annotation
around it and one ``bench.<call>`` annotation around each call the
mode makes into the program, so that idle gaps on the device can be
put down to what the host was doing. The profiler stops at the first
call that starts ``TRACE_S`` seconds into the window (a window of one
call is traced whole): the traced window, which the per-layer metrics
read, is its first seconds.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import tempfile
import time
from contextlib import contextmanager
from typing import Optional

import jax

TRACE_S = 2.0

# JAX's duration events that mean a program was traced or compiled.
_JIT_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
               "/jax/core/compile/backend_compile_duration": "compiles"}


class _JitEvents:
    """Counts traces and compiles in this process, from the moment the
    first window opens (one listener a process; it never raises)."""

    counts = {"traces": 0, "compiles": 0}
    _registered = False

    @classmethod
    def register(cls):
        if not cls._registered:
            jax.monitoring.register_event_duration_secs_listener(cls._on)
            cls._registered = True

    @classmethod
    def _on(cls, event, duration, **kw):
        kind = _JIT_EVENTS.get(event)
        if kind:
            cls.counts[kind] += 1


@dataclasses.dataclass
class Run:
    """One run's arguments and what it measured."""

    seed: int
    seconds: float
    trace: bool
    cfg: dict
    traffic: dict
    t_start: float                      # process start, perf_counter
    setup_s: Optional[float] = None
    window_s: Optional[float] = None
    trace_summary: object = None        # bench.tracefile.Summary
    spans: list = dataclasses.field(default_factory=list)
    counts: dict = dataclasses.field(default_factory=dict)
    phases: dict = dataclasses.field(default_factory=dict)  # set-up, s
    memory_peak_bytes: Optional[int] = None


def memory_peak_bytes() -> Optional[int]:
    """Peak bytes in use on the fullest device, where the backend says."""
    peaks = []
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class Window:
    """The measured window of one run.

    ``with Window(run) as w:`` marks the end of set-up (``run.setup_s``),
    clears the program's span recorder, and on exit records the
    window's length, the program's spans and the device memory peak;
    with tracing on it also profiles the window and reduces the trace.
    ``w.call(name)`` annotates one call into the program, and
    ``w.add(rows=n)`` counts the work a call completed, so that the
    traced part of the window has its own totals (``run.counts["traced"]``).

    Set-up's garbage is collected before the window opens; in the window
    the garbage collector runs as it would in a deployment, and its
    pauses are counted (``run.counts["gc"]``).
    """

    def __init__(self, run: Run):
        self.run = run
        self._dir = None
        self._ann = None
        self.totals = {}

    def __enter__(self):
        from repro.obs import trace as obs_trace
        obs_trace.TRACER.reset()
        gc.collect()
        self._gc = {"collections": 0, "total_s": 0.0, "max_s": 0.0}
        self._gc_t = None
        gc.callbacks.append(self._gc_cb)
        _JitEvents.register()
        self._jit0 = dict(_JitEvents.counts)
        if self.run.trace:
            self._dir = tempfile.mkdtemp(prefix="bench-trace-")
            # Host TraceMe events only: the Python tracer would add a
            # cost to every Python call of the window.
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self._dir, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation("bench.window")
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        self.run.setup_s = self.t0 - self.run.t_start
        return self

    def call(self, name: str):
        if not self.run.trace or self._ann is None:
            return _NULL
        if time.perf_counter() - self.t0 >= TRACE_S:
            self._stop_trace()
            return _NULL
        return jax.profiler.TraceAnnotation(f"bench.{name}")

    def _gc_cb(self, phase, info):
        if phase == "start":
            self._gc_t = time.perf_counter()
        elif self._gc_t is not None:
            d = time.perf_counter() - self._gc_t
            self._gc["collections"] += 1
            self._gc["total_s"] += d
            self._gc["max_s"] = max(self._gc["max_s"], d)

    def add(self, **counts):
        for k, v in counts.items():
            self.totals[k] = self.totals.get(k, 0) + v

    def _stop_trace(self):
        self._ann.__exit__(None, None, None)
        self._ann = None
        jax.profiler.stop_trace()
        self.run.counts["traced"] = dict(self.totals)

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.run.window_s = t1 - self.t0
        if self._ann is not None:
            self._stop_trace()
        gc.callbacks.remove(self._gc_cb)
        self.run.counts.update(self.totals)
        self.run.counts["gc"] = self._gc
        self.run.counts["jit"] = {k: v - self._jit0[k]
                                  for k, v in _JitEvents.counts.items()}
        from repro.obs import trace as obs_trace
        self.run.spans = [(e.name, e.dur_ns) for e in
                          obs_trace.TRACER.events()]
        self.run.memory_peak_bytes = memory_peak_bytes()
        if self.run.trace and exc[0] is None:
            from bench import tracefile
            try:
                self.run.trace_summary = tracefile.reduce_dir(self._dir)
            finally:
                shutil.rmtree(self._dir, ignore_errors=True)
        elif self._dir:
            shutil.rmtree(self._dir, ignore_errors=True)
        return False


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()


@contextmanager
def timed(out: dict, key: str):
    t0 = time.perf_counter()
    yield
    out[key] = time.perf_counter() - t0
