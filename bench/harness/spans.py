"""The program's own spans in a ``jax.profiler`` trace.

``repro.obs.span`` puts host spans named ``octopus/...`` inside the
program's calls: ``octopus/cohort`` around each cohort of
``CohortEngine.round`` with its children ``octopus/cohort/deploy``,
``.../dispatch``, ``.../pull`` and ``.../fold``, and
``octopus/server/merge`` around ``OctopusServer.merge_stats``. They are
on the profiler's clock, beside the device ops and the benchmark's own
``bench/`` spans.

``from_xplane`` reads a trace as ``trace.from_xplane`` does and keeps
the host events of both prefixes. ``reduce`` takes from it, for the
window, each program span's durations and the idle gaps of the first
device charged to the innermost span of either prefix that covers their
middle, as ``trace.reduce`` charges them to ``bench/`` spans; the lookup
is one sort and a sweep, so thousands of spans and tens of thousands of
gaps cost well under a second.
"""
from __future__ import annotations

import glob
import heapq
import os
import statistics
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

from . import trace as T

PROGRAM_PREFIX = "octopus/"
HOST_PREFIXES = ("bench/", PROGRAM_PREFIX)


def from_xplane(directory: str) -> T.Trace:
    """Read the newest ``.xplane.pb`` under ``directory``: device ops,
    and host events named ``bench/...`` or ``octopus/...``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    data = ProfileData.from_file(paths[-1])
    trace = T.Trace()
    for plane in data.planes:
        if plane.name.startswith(T.DEVICE_PREFIX):
            trace.devices[plane.name] = [
                T.Event(e.name, e.start_ns, e.duration_ns)
                for line in plane.lines if line.name == T.OPS_LINE
                for e in line.events]
        elif plane.name.startswith("/host:"):
            trace.host += [T.Event(e.name, e.start_ns, e.duration_ns)
                           for line in plane.lines for e in line.events
                           if e.name.startswith(HOST_PREFIXES)]
    return trace


def innermost(spans: Sequence[T.Event], points: Sequence[float]
              ) -> List[str]:
    """For each point, the name of the shortest span other than the
    window that covers it (``start <= t < end``; the first listed among
    equals), or ``trace.NO_SPAN``.

    Spans enter a heap keyed by duration as the sweep passes their
    start; a span at the top that has ended is dropped, since no later
    point lies inside it."""
    order = sorted(range(len(points)), key=points.__getitem__)
    cands = sorted((s.start_ns, i) for i, s in enumerate(spans)
                   if s.name != T.WINDOW_SPAN)
    heap: List[Tuple[float, int]] = []
    out = [T.NO_SPAN] * len(points)
    j = 0
    for k in order:
        t = points[k]
        while j < len(cands) and cands[j][0] <= t:
            i = cands[j][1]
            heapq.heappush(heap, (spans[i].dur_ns, i))
            j += 1
        while heap and spans[heap[0][1]].end_ns <= t:
            heapq.heappop(heap)
        if heap:
            out[k] = spans[heap[0][1]].name
    return out


@dataclass
class Spans:
    """The program's spans in one traced window."""
    window_s: float
    idle_s: float                           # first device, in the window
    durations: Dict[str, List[float]]       # seconds, spans starting inside
    idle_gaps: List[Tuple[str, float]]      # idle seconds by innermost span

    def median_ms(self, name: str) -> Optional[float]:
        """Median duration of span ``name`` in ms; None if absent."""
        d = self.durations.get(name)
        return 1e3 * statistics.median(d) if d else None

    @property
    def program_idle_share(self) -> float:
        """Share of the idle time charged to ``octopus/`` spans."""
        charged = sum(s for n, s in self.idle_gaps
                      if n.startswith(PROGRAM_PREFIX))
        return charged / self.idle_s if self.idle_s > 0 else 0.0


def reduce(trace: T.Trace, device: str) -> Spans:
    """The window's program spans and the idle gaps of ``device``."""
    lo, hi = T.window_of(trace)
    durations: Dict[str, List[float]] = {}
    for e in trace.host:
        if e.name.startswith(PROGRAM_PREFIX) and lo <= e.start_ns < hi:
            durations.setdefault(e.name, []).append(e.dur_ns * 1e-9)
    ops = trace.devices.get(device, [])
    edges = [lo] + [x for iv in T.union(T.clip(ops, lo, hi)) for x in iv] \
        + [hi]
    gaps = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
    names = innermost(trace.host, [(s + e) / 2 for s, e in gaps])
    by_name: Dict[str, float] = {}
    for (s, e), name in zip(gaps, names):
        by_name[name] = by_name.get(name, 0.0) + (e - s) * 1e-9
    return Spans(window_s=(hi - lo) * 1e-9,
                 idle_s=sum(e - s for s, e in gaps) * 1e-9,
                 durations=durations,
                 idle_gaps=sorted(by_name.items(), key=lambda kv: -kv[1]))
