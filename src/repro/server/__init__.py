"""Continuous-ingest code-server runtime (OCTOPUS Step 6 at production
scale).

  store      — CodeStore: one capacity-bounded, versioned, lazily-decoded
               ring buffer of packed transmissions; ShardedCodeStore:
               independent ring buffers per (codebook version, client
               shard) partition
  registry   — CodebookRegistry: immutable per-merge dictionary snapshots
               + staleness-weighted Step 5 merge + rolling
               MigrationWindow (keep / retire / reencode policies)
  scheduler  — RoundScheduler: partial participation, stragglers, drops,
               client churn, open-ended Poisson arrivals — deterministic
               under one PRNG key
  multitask  — MultiTaskTrainer: N downstream heads from ONE bulk decode
  runtime    — ContinuousIngestService: clocked, admission-controlled
               ingest (backpressure verdicts, exactly-once dedup window,
               background bulk decode under a BulkDecodePolicy) with
               journaled crash recovery (``recover``); UplinkQueue: the
               in-flight payloads and the measured §2.8 byte ledger it
               shares with ``repro.sim``'s ``CohortEngine.run_traffic``
  persist    — ServerPersistence: append-only ingest journal + atomic
               periodic snapshots of the full durable state
"""
from repro.wire.payload import CodePayload
from repro.wire.session import AdmissionResult, OctopusServer

from .multitask import MultiTaskTrainer, TaskSpec
from .persist import ServerPersistence
from .registry import (MIGRATION_POLICIES, CodebookRegistry,
                       MigrationWindow)
from .runtime import (BulkDecodePolicy, ContinuousIngestService, TickStats,
                      UplinkQueue)
from .scheduler import (STANDARD_SCENARIOS, DiurnalProfile, RoundEvent,
                        RoundScheduler, Scenario, SchedulerConfig)
from .store import CodeStore, ShardedCodeStore, StoreRecord

__all__ = ["AdmissionResult", "BulkDecodePolicy",
           "CodePayload", "CodeStore", "CodebookRegistry",
           "ContinuousIngestService", "DiurnalProfile",
           "MIGRATION_POLICIES", "MigrationWindow", "MultiTaskTrainer",
           "OctopusServer", "RoundEvent", "RoundScheduler",
           "STANDARD_SCENARIOS", "Scenario", "SchedulerConfig",
           "ServerPersistence", "ShardedCodeStore", "StoreRecord",
           "TaskSpec", "TickStats", "UplinkQueue"]
