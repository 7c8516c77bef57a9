"""Batched multi-client OCTOPUS simulation (ROADMAP: client populations
at scale, not one Python object per client).

  engine  — stacked ClientState pytrees + one jitted vmap/shard_map round
            program (fine-tune, encode, the fused Steps 3-5 tail) and a
            one-dispatch deploy; the round's uplink is a
            ``repro.wire.CodePayload``
  cohort  — cohort-streamed population rounds (100k+ clients): fixed-size
            cohorts through ONE compiled engine round, exactly
            associative Step-5 stats merge, scheduler-driven traffic +
            open-ended continuous-ingest traffic
  faults  — FaultPlan / FaultyChannel: deterministic chaos (drop /
            duplicate / reorder / delay / corrupt / truncate) between
            the cohort engine and the ingest service, plus the client
            retry loop over the exactly-once dedup window

The PR-1 ``IngestBuffer`` and the ``PackedCodes`` payload alias are
RETIRED: importing either raises with a pointer at the unified wire
layer (``repro.wire`` / ``repro.server``).
"""
from repro.wire.payload import CodePayload

from .cohort import (CohortEngine, CohortPlan, CohortRound, ContinuousTick,
                     TrafficRound)
from .engine import (SimEngine, client_batch_size, replicate_clients,
                     stack_clients, unstack_clients)
from .faults import FAULT_KINDS, FaultPlan, FaultyChannel

__all__ = ["CodePayload", "CohortEngine", "CohortPlan", "CohortRound",
           "ContinuousTick", "FAULT_KINDS", "FaultPlan", "FaultyChannel",
           "SimEngine", "TrafficRound", "client_batch_size",
           "replicate_clients", "stack_clients", "unstack_clients"]

_TOMBSTONES = {
    "IngestBuffer": "repro.server.CodeStore / repro.server.ShardedCodeStore",
    "PackedCodes": "repro.wire.CodePayload",
}


def __getattr__(name):
    if name in _TOMBSTONES:
        raise ImportError(
            f"repro.sim.{name} was removed; use {_TOMBSTONES[name]} "
            f"(the unified wire carrier/store — see repro.wire)")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
