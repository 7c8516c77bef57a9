"""Cohort-streamed population rounds (OCTOPUS §2.2 at 100k+ clients).

``SimEngine`` advances a stacked population in ONE fused dispatch — but
stacking 100k clients' full DVQ-AE states (plus their latents and packed
uplinks) in a single round is exactly the whole-population
materialization the paper's cross-device regime forbids. This module
streams the round instead:

  * :class:`CohortPlan` partitions the participating slot ids into
    fixed-size cohorts. Each cohort flows through the SAME jitted
    ``SimEngine`` round (one vmapped encode + ONE fused
    quantize-pack-stats dispatch, ``shard_map`` over the mesh 'data'
    axis when a mesh is given) — so peak memory is one COHORT's state,
    never the population's.
  * Per-cohort Step-5 contributions are folded into an
    **exactly associative** accumulator (``repro.core.ema.MergeStats``,
    int64 fixed point): any cohort grouping or order of the same client
    set produces the bit-identical merged dictionary
    (``octopus.server_merge_stats``). Grouping is invisible — the
    correctness contract the property suite (tests/test_cohort.py) pins.
  * Per-cohort :class:`~repro.wire.CodePayload` uplinks stream into
    ``OctopusServer.ingest`` unchanged; because every client record is
    padded to whole super-groups INDIVIDUALLY, Σ cohort ``nbytes`` ==
    the whole-population round's measured bytes (§2.8 accounting is
    cohort-invariant), and concatenating cohort payloads
    (``wire.concat_payloads``) reproduces the population payload
    bit-for-bit.
  * :meth:`CohortEngine.run_traffic` drives rounds from a
    ``RoundScheduler`` — diurnal participation (``DiurnalProfile``)
    arrives in whole cohorts, stragglers/drops ride the shared
    ``UplinkQueue`` at cohort granularity (cohorts are carved WITHIN
    each (delay, dropped) delivery group, so every payload is uniform).

Clients deploy FRESH from the server each round (cross-device regime:
the population's per-slot state lives on the devices, not the server) —
the server never holds more than one cohort's state at a time.

Bit-invariance boundary: the engine-level guarantee covers cohorts of
>= 2 clients. XLA compiles the degenerate C == 1 vmap into a different
program (last-ulp drift in the conv stack), so ``CohortPlan.build``
never emits a singleton tail; the MERGE algebra itself
(``core.ema.MergeStats``) is exact for any grouping including
singletons, given per-client statistics.

Typical use::

    eng = CohortEngine(cfg, gamma=0.99, n_local_steps=0)
    plan = CohortPlan.build(np.arange(100_000), cohort_size=1024)
    out = eng.round(server, plan, data_fn)     # streams 98 cohorts
    server = OC.server_merge_stats(server, out.stats)
"""
from __future__ import annotations

import time
from typing import Callable, List, NamedTuple, Optional, Tuple

import numpy as np

from repro.core import octopus as OC
from repro.core.dvqae import DVQAEConfig
from repro.core.ema import (MergeStats, merge_stats, merge_stats_add,
                            merge_stats_zero)
from repro.obs import recorder as _obs
from repro.wire.payload import CodePayload

from .engine import SimEngine

DataFn = Callable[[np.ndarray], object]     # slot ids -> (len(ids), B, ...)


class CohortPlan(NamedTuple):
    """A partition of participating slot ids into cohorts."""
    cohorts: Tuple[np.ndarray, ...]

    @classmethod
    def build(cls, members, cohort_size: int) -> "CohortPlan":
        """Chop ``members`` (slot ids, kept in order) into consecutive
        cohorts of ``cohort_size`` (the tail cohort may be smaller).

        A size-1 tail is folded into the previous cohort instead: XLA
        specializes the degenerate single-client batch into a DIFFERENT
        program than any C>=2 vmap (last-ulp float drift in the conv
        stack), which would break the engine-level bit-invariance the
        property suite pins — and it would burn a compile on a shape
        used once.
        """
        m = np.asarray(members, dtype=int).reshape(-1)
        if m.size == 0:
            raise ValueError("CohortPlan needs at least one member")
        cs = int(cohort_size)
        if cs < 1:
            raise ValueError(f"cohort_size must be >= 1, got {cs}")
        cohorts = [m[i:i + cs] for i in range(0, m.size, cs)]
        if cs > 1 and len(cohorts) > 1 and cohorts[-1].size == 1:
            tail = cohorts.pop()
            cohorts[-1] = np.concatenate([cohorts[-1], tail])
        return cls(cohorts=tuple(cohorts))

    @classmethod
    def from_groups(cls, groups) -> "CohortPlan":
        """Arbitrary (possibly ragged) explicit grouping — the property
        suite uses this to assert grouping-invariance."""
        cohorts = tuple(np.asarray(g, dtype=int).reshape(-1)
                        for g in groups)
        if not cohorts or any(c.size == 0 for c in cohorts):
            raise ValueError("every cohort needs at least one member")
        return cls(cohorts=cohorts)

    @property
    def n_cohorts(self) -> int:
        return len(self.cohorts)

    @property
    def sizes(self) -> Tuple[int, ...]:
        return tuple(int(c.size) for c in self.cohorts)

    @property
    def members(self) -> np.ndarray:
        return np.concatenate(self.cohorts)

    @property
    def n_clients(self) -> int:
        return int(sum(self.sizes))


class CohortRound(NamedTuple):
    """One streamed population round."""
    payloads: Tuple[CodePayload, ...]   # one per cohort, ingest-ready
    stats: MergeStats                   # associative Step-5 accumulator
    n_clients: int
    nbytes: int                         # Σ measured cohort uplink bytes


class TrafficRound(NamedTuple):
    """Per-round ledger of a scheduler-driven traffic run."""
    round: int
    n_participants: int
    n_cohorts: int
    bytes_sent: int
    bytes_delivered: int
    merged_version: Optional[int]


class ContinuousTick(NamedTuple):
    """Per-tick ledger of an open-ended continuous-ingest run."""
    tick: int
    n_participants: int
    n_cohorts: int
    bytes_offered: int       # measured bytes at the door (incl. refusals)
    bytes_delivered: int     # landed in the store this tick
    n_rejected: int          # admission rejections this tick
    n_deferred: int          # admissions answered "back off"
    merged_version: Optional[int]


class CohortEngine:
    """Streams population rounds cohort-by-cohort through ONE SimEngine.

    The inner engine's jit cache keys on the cohort shape, so every
    same-size cohort reuses one compiled round; a ragged tail cohort
    costs exactly one extra compile.
    """

    def __init__(self, cfg: DVQAEConfig, *, lr: float = 1e-4,
                 gamma: float = 0.99, n_local_steps: int = 1, mesh=None):
        self.cfg = cfg
        self.engine = SimEngine(cfg, lr=lr, gamma=gamma,
                                n_local_steps=n_local_steps, mesh=mesh)
        self.bits = self.engine.bits

    # ------------------------------------------------------------- rounds

    def round(self, server: OC.ServerState, plan: CohortPlan,
              data_fn: DataFn, *, version: int = 0,
              labels_fn: Optional[DataFn] = None,
              round_idx: Optional[int] = None) -> CohortRound:
        """Steps 2-5 for ``plan``'s population, one cohort at a time.

        ``data_fn(slot_ids)`` returns the cohort's local batches
        ``(len(slot_ids), B, ...)`` — keyed by slot id, so the SAME
        client sees the SAME data under any cohort grouping (that is
        what makes grouping-invariance testable). Clients deploy fresh
        from ``server``; per-cohort payloads are stamped ``version``.
        ``round_idx`` only labels the flight recorder's per-cohort
        encode events (the computation never reads it).

        Each cohort runs inside the span ``octopus/cohort`` (args
        ``cohort``, ``clients``, ``version`` and ``positions``, the latent
        positions of one client record) and its four children in order:
        ``cohort/deploy`` (fresh clients in one compiled dispatch,
        sharded over 'data' on a mesh; labels, data),
        ``cohort/dispatch`` (enqueue the jitted round),
        ``cohort/pull`` (codebooks and EMA counts to the host: the only
        wait on the device) and ``cohort/fold`` (into the accumulator).
        """
        K, M = server.params["codebook"].shape
        stats = merge_stats_zero(int(K), int(M))
        payloads: List[CodePayload] = []
        for i, cohort in enumerate(plan.cohorts):
            with _obs.span("cohort", event="encode", cohort=i,
                           clients=int(cohort.size),
                           version=int(version)) as span:
                with _obs.span("cohort/deploy"):
                    clients = self.engine.init_clients(server,
                                                       int(cohort.size))
                    labels = None if labels_fn is None else labels_fn(cohort)
                    data = data_fn(cohort)
                with _obs.span("cohort/dispatch"):
                    clients, payload = self.engine.round(
                        clients, data, version=version, labels=labels)
                # index shape (C, B, T[, n_c]): samples x positions each
                span.add(positions=int(np.prod(payload.shape[1:3])))
                # the one place the host waits on the device
                with _obs.span("cohort/pull"):
                    codebooks = np.asarray(clients.params["codebook"])
                    counts = np.asarray(clients.ema.counts)
                # fold this cohort's Step-5 contribution in; per-client
                # fixed-point quantization is grouping-independent, so the
                # integer totals match the single-shot population merge
                with _obs.span("cohort/fold"):
                    stats = merge_stats_add(stats,
                                            merge_stats(codebooks, counts))
                payloads.append(payload)
                if span.recording:
                    fields = {"cohort_size": int(cohort.size)}
                    if round_idx is not None:
                        fields["round"] = int(round_idx)
                    span.add(**fields, **_obs.payload_meta(payload))
        return CohortRound(payloads=tuple(payloads), stats=stats,
                           n_clients=plan.n_clients,
                           nbytes=sum(p.nbytes for p in payloads))

    # ------------------------------------------------------------ traffic

    def run_traffic(self, wire, scheduler, data_fn: DataFn, *,
                    cohort_size: int, n_rounds: int, merge_every: int = 0,
                    labels_fn: Optional[DataFn] = None,
                    queue=None) -> List[TrafficRound]:
        """Scheduler-driven rounds streaming into ``wire`` (an
        ``OctopusServer``).

        Each round: one ``RoundScheduler.step()`` decides participation
        (diurnal profiles arrive in whole cohorts via the scheduler's
        ``quantum``); participants are carved into cohorts WITHIN each
        (straggler delay, dropped) delivery group so every cohort
        payload has a uniform fate on the shared :class:`UplinkQueue`;
        due payloads land through ``wire.ingest`` unchanged. Every
        ``merge_every`` rounds the accumulated associative stats finish
        the Step-5 merge (``wire.merge_stats``) and register a new
        codebook version — subsequent cohorts pack under it.
        """
        from repro.server.runtime import UplinkQueue
        if queue is None:
            queue = UplinkQueue()
        acc: Optional[MergeStats] = None
        history: List[TrafficRound] = []
        for _ in range(n_rounds):
            rec = _obs.active()
            t0 = time.perf_counter() if rec is not None else 0.0
            ev = scheduler.step()
            groups = {}
            for j, slot in enumerate(ev.participants):
                key = (int(ev.delays[j]), bool(ev.dropped[j]))
                groups.setdefault(key, []).append(int(slot))
            sent = n_cohorts = 0
            for (delay, dropped), slots in sorted(groups.items()):
                plan = CohortPlan.build(slots, cohort_size)
                out = self.round(wire.state, plan, data_fn,
                                 version=wire.version, labels_fn=labels_fn,
                                 round_idx=ev.round)
                for payload, cohort in zip(out.payloads, plan.cohorts):
                    sent += queue.send(payload, round=ev.round,
                                       delay=delay, dropped=dropped,
                                       client_ids=cohort)
                if not dropped:
                    # dropped uplinks burn bytes AND lose their Step-5
                    # contribution — the radio ate the whole packet
                    acc = out.stats if acc is None else \
                        merge_stats_add(acc, out.stats)
                n_cohorts += plan.n_cohorts
            delivered, _ = queue.deliver(wire, ev.round)
            merged_version = None
            if merge_every and (ev.round + 1) % merge_every == 0 \
                    and acc is not None:
                merged_version = wire.merge_stats(acc)
                acc = None
            history.append(TrafficRound(
                round=ev.round, n_participants=int(ev.participants.size),
                n_cohorts=n_cohorts, bytes_sent=sent,
                bytes_delivered=delivered, merged_version=merged_version))
            if rec is not None:
                dur_ms = (time.perf_counter() - t0) * 1e3
                rec.event("round", round=ev.round,
                          n_participants=int(ev.participants.size),
                          n_cohorts=n_cohorts, bytes_sent=sent,
                          bytes_delivered=delivered,
                          queue_depth=len(queue),
                          merged_version=merged_version, dur_ms=dur_ms)
                rec.metrics.set_gauge("uplink_queue_depth", len(queue))
        return history

    def run_continuous(self, service, scheduler, data_fn: DataFn, *,
                       cohort_size: int, n_ticks: int, merge_every: int = 0,
                       labels_fn: Optional[DataFn] = None,
                       migration_policy: Optional[str] = None
                       ) -> List[ContinuousTick]:
        """Open-ended traffic into a ``ContinuousIngestService``.

        The round-quantized loop inverted: each tick the scheduler draws
        an arrival count (set ``SchedulerConfig.rate`` for Poisson
        arrivals — quiet ticks and bursts both happen), arrivals are
        carved into cohorts per (delay, dropped) fate and OFFERED to the
        service one cohort-payload at a time, and the service clock
        ticks once. Admission is the service's call: a cohort whose
        offer comes back ``rejected`` (full queue, radio drop, wire
        violation) loses its Step-5 contribution along with its payload
        — backpressure reaches the merge, not just the store.

        Every ``merge_every`` ticks the accumulated associative stats
        finish the Step-5 merge. With ``migration_policy`` set, each
        merge also runs a rolling codebook upgrade: any open migration
        window is completed (applying the policy to old-version
        records), then a fresh ``latest-1 -> latest`` window opens — so
        in-flight payloads packed under the previous dictionary ingest
        as ``migrated`` while new cohorts pack under the merged one.
        """
        wire = service.wire
        acc: Optional[MergeStats] = None
        history: List[ContinuousTick] = []
        for _ in range(n_ticks):
            ev = scheduler.step()
            groups = {}
            for j, slot in enumerate(ev.participants):
                key = (int(ev.delays[j]), bool(ev.dropped[j]))
                groups.setdefault(key, []).append(int(slot))
            offered = n_cohorts = n_rej = n_def = 0
            for (delay, dropped), slots in sorted(groups.items()):
                plan = CohortPlan.build(slots, cohort_size)
                for cohort in plan.cohorts:
                    out = self.round(wire.state,
                                     CohortPlan.from_groups([cohort]),
                                     data_fn, version=wire.version,
                                     labels_fn=labels_fn,
                                     round_idx=ev.round)
                    res = service.offer(out.payloads[0], client_ids=cohort,
                                        delay=delay, dropped=dropped)
                    offered += res.nbytes
                    if res.verdict == "rejected":
                        n_rej += 1
                    elif res.verdict == "duplicate":
                        pass    # a retransmit raced in; counted once already
                    else:
                        if res.verdict == "deferred":
                            n_def += 1
                        # only admitted cohorts reach the Step-5 merge
                        acc = out.stats if acc is None else \
                            merge_stats_add(acc, out.stats)
                n_cohorts += plan.n_cohorts
            merged_version = None
            if merge_every and (ev.round + 1) % merge_every == 0 \
                    and acc is not None:
                # merge + migration go through the SERVICE delegates so
                # they journal (crash consistency) and compose with a
                # FaultyChannel wrapping the service
                merged_version = service.merge_stats(acc)
                acc = None
                if migration_policy is not None:
                    if wire.registry.migration is not None:
                        service.complete_migration()
                    service.begin_migration(policy=migration_policy)
            ts = service.tick(
                merged_version=merged_version,
                extra_fields={"n_participants": int(ev.participants.size),
                              "n_cohorts": n_cohorts})
            history.append(ContinuousTick(
                tick=ts.tick, n_participants=int(ev.participants.size),
                n_cohorts=n_cohorts, bytes_offered=offered,
                bytes_delivered=ts.bytes_delivered, n_rejected=n_rej,
                n_deferred=n_def, merged_version=merged_version))
        return history
