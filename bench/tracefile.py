"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``. On a TPU each chip is a plane named
``/device:TPU:<n>`` whose ``XLA Ops`` line holds one event per HLO
instruction run on the core; a Pallas kernel is a ``custom-call``
instruction named after the kernel (``%am_search_packed.1 = ...
custom-call(...)``). Host threads are lines of the ``/host:CPU`` plane;
the benchmark's own ``bench.*`` annotations and JAX's dispatch events
are on the Python thread's line.

* busy: the union of the op intervals of one device inside the window,
  averaged over the devices;
* window: the ``bench.window`` annotation's interval;
* per-op device time: durations summed by instruction name and result
  shape, and per kernel by the custom-call's name;
* idle gaps: the intervals between busy stretches, each named by the
  innermost host event on the Python thread that covers its middle.
"""
from __future__ import annotations

import dataclasses
import glob
import re
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
GAPS = 10  # longest idle gaps named in the breakdown
# The instruction's own opcode is " custom-call(", an operand that is
# one reads "%custom-call".
_KERNEL = re.compile(r"^%([A-Za-z_]\w*?)(?:\.\d+)? = .* custom-call\(")


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                      # mean over devices
    op_s: Dict[str, float]             # instruction name -> seconds
    kernel_s: Dict[str, float]         # custom-call kernel -> seconds
    kernel_count: Dict[str, int]
    gaps: List[Tuple[str, float]]      # the longest idle gaps: (host
                                       # activity, seconds), longest first
    devices: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:10]]}


_RESULT = re.compile(r"^\(?([a-z0-9]+\[[0-9,]*\])")


def op_name(event_name: str) -> str:
    """The instruction's name and the shape of its (first) result:
    ``%fusion.3 = u8[1024,128,3072]{...} fusion(...)`` ->
    ``fusion.3 u8[1024,128,3072]``."""
    name, _, rest = event_name.partition(" = ")
    m = _RESULT.match(rest)
    return name.lstrip("%") + (" " + m.group(1) if m else "")


def kernel_name(event_name: str) -> Optional[str]:
    m = _KERNEL.match(event_name)
    return m.group(1) if m else None


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce(planes) -> Summary:
    """``planes``: iterable of objects with ``name`` and ``lines``, each
    line with ``name`` and ``events`` (``name``, ``start_ns``,
    ``duration_ns``), as ``ProfileData.planes`` gives them."""
    planes = list(planes)
    host_events = []
    window = None
    for p in planes:
        if p.name != "/host:CPU":
            continue
        for line in p.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events]
            for name, s, e in evs:
                if name == WINDOW:
                    window = (s, e)
            if any(n.startswith("bench.") for n, _, _ in evs):
                host_events = evs
    if window is None:
        raise ValueError("no bench.window annotation in the trace")
    lo, hi = window

    op_s: Dict[str, float] = {}
    kernel_s: Dict[str, float] = {}
    kernel_count: Dict[str, int] = {}
    busy = []
    raw_gaps: List[Tuple[float, float]] = []
    devices = [p for p in planes if p.name.startswith("/device:TPU:")]
    for p in devices:
        ivs = []
        for line in p.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                s, t = e.start_ns, e.start_ns + e.duration_ns
                if t <= lo or s >= hi:
                    continue
                ivs.append((s, t))
                dur = (min(t, hi) - max(s, lo)) / 1e9
                name = op_name(e.name)
                op_s[name] = op_s.get(name, 0.0) + dur
                k = kernel_name(e.name)
                if k:
                    kernel_s[k] = kernel_s.get(k, 0.0) + dur
                    kernel_count[k] = kernel_count.get(k, 0) + 1
        merged = merge(clip(ivs, lo, hi))
        busy.append(sum(e - s for s, e in merged) / 1e9)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        raw_gaps += [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    raw_gaps.sort(key=lambda g: g[0] - g[1])
    gaps = [(_host_at((s + e) / 2, host_events), (e - s) / 1e9)
            for s, e in raw_gaps[:GAPS]]
    n = max(len(devices), 1)
    return Summary(window_s=(hi - lo) / 1e9, busy_s=sum(busy) / n,
                   op_s=op_s, kernel_s=kernel_s,
                   kernel_count=kernel_count, gaps=gaps, devices=len(devices))


def _host_at(t: float, events) -> str:
    """Innermost host event covering ``t`` (the shortest that does)."""
    best = None
    for name, s, e in events:
        if s <= t <= e and name != WINDOW and (best is None
                                               or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "host: no annotated activity"


def reduce_dir(trace_dir: str) -> Summary:
    from jax.profiler import ProfileData
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one trace under {trace_dir}, "
                         f"found {paths}")
    return reduce(ProfileData.from_file(paths[0]).planes)
