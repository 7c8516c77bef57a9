"""From a ``jax.profiler`` trace to device busy time, kernel time and the
breakdown a result line carries.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
``from_xplane`` reads it (with nothing but JAX) into plain lists of
events, and everything after that works on those lists, so a test can
build a trace by hand:

* device ops: the events of each TPU plane's ``XLA Ops`` line, named by
  their HLO text (a Pallas kernel: ``%<wrapper name>.<n> = ...
  custom-call(...)``);
* host spans: the events whose names start with ``bench/`` — the
  ``TraceAnnotation`` spans the benchmark puts around its calls into
  each layer, and ``bench/window`` around the whole traced window.

Busy time is the union of a device's op intervals inside the window,
averaged over the devices the cell uses. An idle gap of the first
device is charged to the innermost host span that covers its middle.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench/window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
NO_SPAN = "outside bench spans"
NAME_CHARS = 160


@dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    devices: Dict[str, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)


def from_xplane(directory: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``directory``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    data = ProfileData.from_file(paths[-1])
    trace = Trace()
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops += [Event(e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
            trace.devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench/"):
                        trace.host.append(Event(e.name, e.start_ns,
                                                e.duration_ns))
    return trace


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merge overlapping [start, end) intervals."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(events: Sequence[Event], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    return [(max(e.start_ns, lo), min(e.end_ns, hi)) for e in events
            if e.end_ns > lo and e.start_ns < hi]


@dataclass
class Reduced:
    """What the per-layer readers and the result line take from a trace."""
    window_s: float
    busy_s: float                           # averaged over the devices
    kernel_ns: Dict[str, float]             # pattern -> summed op time
    device_ops: List[Tuple[str, float]]     # top ops by summed seconds
    idle_gaps: List[Tuple[str, float]]      # idle seconds by host span

    def kernel_seconds(self, pattern: str) -> Optional[float]:
        ns = self.kernel_ns.get(pattern, 0.0)
        return ns * 1e-9 if ns > 0 else None

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def window_of(trace: Trace) -> Tuple[float, float]:
    spans = [e for e in trace.host if e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    w = max(spans, key=lambda e: e.dur_ns)
    return w.start_ns, w.end_ns


def _innermost(spans: Sequence[Event], t: float) -> str:
    best = None
    for s in spans:
        if s.start_ns <= t < s.end_ns and s.name != WINDOW_SPAN:
            if best is None or s.dur_ns < best.dur_ns:
                best = s
    return best.name if best is not None else NO_SPAN


def reduce(trace: Trace, devices: Sequence[str],
           kernels: Sequence[str] = (), top: int = 10) -> Reduced:
    """Reduce the window of ``trace`` over the device planes ``devices``.

    ``kernels``: op name prefixes (an op's name is its HLO text, which
    starts with ``%<instruction name>``) whose summed time, averaged over
    those devices, the readers ask for. Op names in ``device_ops`` are
    cut to ``NAME_CHARS``."""
    lo, hi = window_of(trace)
    busy: Dict[str, float] = {}
    per_op: Dict[str, float] = {}
    kernel_ns = {k: 0.0 for k in kernels}
    first = None
    for dev in devices:
        ops = [e for e in trace.devices.get(dev, [])
               if e.end_ns > lo and e.start_ns < hi]
        merged = union(clip(ops, lo, hi))
        busy[dev] = sum(e - s for s, e in merged) * 1e-9
        if first is None:
            first = merged
        for e in ops:
            d = min(e.end_ns, hi) - max(e.start_ns, lo)
            op = e.name[:NAME_CHARS]
            per_op[op] = per_op.get(op, 0.0) + d
            for k in kernels:
                if e.name.startswith(k):
                    kernel_ns[k] += d
    gaps: Dict[str, float] = {}
    edges = [lo] + [x for iv in (first or []) for x in iv] + [hi]
    for s, e in zip(edges[0::2], edges[1::2]):
        if e > s:
            name = _innermost(trace.host, (s + e) / 2)
            gaps[name] = gaps.get(name, 0.0) + (e - s) * 1e-9
    n = max(len(devices), 1)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return Reduced(
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(busy.values()) / n,
        kernel_ns={k: v / n for k, v in kernel_ns.items()},
        device_ops=[(k, v * 1e-9 / n) for k, v in ops],
        idle_gaps=sorted(gaps.items(), key=lambda kv: -kv[1])[:top])
