"""Nested host-side span tracing, on the profiler's clock, with Chrome
trace-event export.

``with span("pad", batch=3): ...`` records a complete event ("ph": "X")
with ``perf_counter_ns`` timestamps; spans nest through a thread-local
stack, so every event carries its own ``span_id`` and its enclosing
``parent_id`` — the double-buffered serving loop's host-prep of batch
k+1 visibly overlaps batch k's device wait when the export is opened
in Perfetto (https://ui.perfetto.dev) or chrome://tracing.

Every span also enters ``jax.profiler.TraceAnnotation`` under its plain
name (the arguments stay out of the TraceMe name), so while a device
profile is being captured the span sits on the Python thread's line of
the profiler's own trace, on the profiler's clock, beside the XLA
activity it caused. Off the profiler the annotation is a sub-microsecond
no-op. The annotation class is resolved on the first span, so importing
this module stays free of jax.

With ``enabled = False`` a span is a shared no-op context: it records
nothing, allocates nothing and enters no annotation.

The recorder is bounded (``max_events``, default 200k, about 55 MB of
events): a long-running serving process must not grow a trace without
limit, so past the cap new events are counted in ``dropped`` instead of
stored.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Dict, List, Optional

# ``jax.profiler.TraceAnnotation``, resolved on the first span; False
# where the profiler API cannot be imported.
_ANNOTATION = None


def _annotation_cls():
    global _ANNOTATION
    if _ANNOTATION is None:
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:
            TraceAnnotation = False
        _ANNOTATION = TraceAnnotation
    return _ANNOTATION


class SpanEvent:
    """One span: the context manager while its body runs, then the
    recorded event (Chrome "X" event), times in ns.

    The arguments are kept as one flat tuple (keys, then values) rather
    than a dict per event; ``args`` rebuilds the dict on demand.
    """

    __slots__ = ("name", "start_ns", "dur_ns", "span_id", "parent_id",
                 "tid", "_args", "_tracer", "_ann")

    def __init__(self, tracer: "Tracer", name: str, args: Dict):
        self.name = name
        self._args = (*args, *args.values()) if args else None
        self._tracer = tracer

    @property
    def args(self) -> Optional[Dict]:
        a = self._args
        if not a:
            return None
        n = len(a) // 2
        return dict(zip(a[:n], a[n:]))

    def __enter__(self):
        tr = self._tracer
        loc = tr._local
        try:
            stack = loc.stack
        except AttributeError:
            stack = loc.stack = []
            loc.tid = threading.get_ident()
        self.tid = loc.tid
        self.span_id = span_id = next(tr._ids)
        self.parent_id = stack[-1] if stack else 0
        stack.append(span_id)
        cls = _ANNOTATION if _ANNOTATION is not None else _annotation_cls()
        if cls:
            ann = self._ann = cls(self.name)
            ann.__enter__()
        else:
            self._ann = None
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.dur_ns = time.perf_counter_ns() - self.start_ns
        ann = self._ann
        if ann is not None:
            ann.__exit__(*exc)
            self._ann = None
        tr = self._tracer
        self._tracer = None
        tr._local.stack.pop()
        with tr._lock:
            if len(tr._events) < tr.max_events:
                tr._events.append(self)
            else:
                tr.dropped += 1
        return False


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class Tracer:
    """Span recorder; one per process is plenty (module ``TRACER``)."""

    def __init__(self, max_events: int = 200_000):
        self.max_events = max_events
        self._lock = threading.Lock()
        self._events: List[SpanEvent] = []
        self._ids = itertools.count(1)
        self._local = threading.local()
        self.dropped = 0
        self.enabled = True

    # -- recording ----------------------------------------------------

    def span(self, name: str, **args):
        """A context manager recording a nested span around its body.

        ``args`` become the event's Chrome-trace ``args`` (stringified
        lazily at export); the profiler annotation carries the name
        alone.
        """
        if not self.enabled:
            return _NULL_SPAN
        return SpanEvent(self, name, args)

    def current_span_id(self) -> int:
        """Id of the innermost open span on this thread (0 = none)."""
        stack = getattr(self._local, "stack", None)
        return stack[-1] if stack else 0

    # -- export -------------------------------------------------------

    def events(self) -> List[SpanEvent]:
        with self._lock:
            return list(self._events)

    def reset(self) -> None:
        with self._lock:
            self._events.clear()
            self.dropped = 0

    def to_chrome_trace(self) -> Dict:
        """The Chrome trace-event JSON object (trace-viewer / Perfetto).

        Timestamps and durations are microseconds (floats are legal);
        thread ids are compacted to small ints in first-seen order so
        the viewer's track names stay readable.
        """
        pid = os.getpid()
        tids: Dict[int, int] = {}
        trace_events: List[Dict] = []
        for ev in self.events():
            tid = tids.setdefault(ev.tid, len(tids))
            args = {"span_id": ev.span_id, "parent_id": ev.parent_id}
            if ev.args:
                args.update({k: _jsonable(v) for k, v in ev.args.items()})
            trace_events.append({
                "name": ev.name,
                "ph": "X",
                "ts": ev.start_ns / 1e3,
                "dur": ev.dur_ns / 1e3,
                "pid": pid,
                "tid": tid,
                "args": args,
            })
        meta = {"dropped_events": self.dropped}
        return {"traceEvents": trace_events, "displayTimeUnit": "ms",
                "otherData": meta}

    def export(self, path: str) -> str:
        """Write the Chrome trace JSON; returns the path."""
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
            f.write("\n")
        return path


def _jsonable(v):
    return v if isinstance(v, (int, float, bool, str, type(None))) else str(v)


# Process-default tracer; ``span`` is the one-liner call sites use.
TRACER = Tracer()
span = TRACER.span
export_chrome_trace = TRACER.export
