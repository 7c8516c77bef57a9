"""The comparisons that decide ``correct``, and the compile counter.

Adapted from the bring-up smoke run's checks (decoded features equal to
the plain gather, the byte ledger identity, ``CompileClock``; its encode
check, the sent codes scored against the float32 reference, lives in the
reference's ``client_round``). Here they return numbers instead of
raising, so that every run prints each number beside its limit, and they
take the reference's own arrays: nothing in this file imports the
program.
"""
from __future__ import annotations

from typing import Dict, NamedTuple

import numpy as np


class Compared(NamedTuple):
    """One number a run compares, and the limit it may not exceed."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


def gather_diff(got, want) -> Dict[str, float]:
    """Decoded features against the plain gather: how many values differ
    and by how much at most (an exact decode reads 0 and 0)."""
    g = np.asarray(got)
    w = np.asarray(want)
    if g.shape != w.shape:
        return {"differ": float(w.size), "max_abs": float("inf")}
    d = np.abs(g.astype(np.float64) - w.astype(np.float64))
    return {"differ": float(np.sum(g != w)), "max_abs": float(d.max())}


def ledger_imbalance(queue) -> int:
    """|sent - (delivered + dropped + rejected + duplicate + in flight)|
    in bytes: the uplink byte ledger's identity, 0 when it holds."""
    return abs(queue.bytes_sent - (queue.bytes_delivered
                                   + queue.bytes_dropped
                                   + queue.bytes_rejected
                                   + queue.bytes_duplicate
                                   + queue.bytes_in_flight))


class CompileClock:
    """XLA compiles and persistent-cache hits, via ``jax.monitoring``.

    A count that moves inside the measured window means a program was
    built or loaded there, which a warmed-up run never does."""

    def __init__(self):
        from jax import monitoring
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def close(self) -> None:
        from jax import monitoring
        monitoring.unregister_event_duration_listener(self._duration)
        monitoring.unregister_event_listener(self._event)

    @property
    def programs(self) -> int:
        """Programs compiled or loaded from the persistent cache."""
        return self.compiles + self.hits
