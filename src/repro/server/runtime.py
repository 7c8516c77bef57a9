"""Continuous-ingest code-server runtime (Step 6 as a first-class
subsystem).

``ContinuousIngestService`` owns the server side of the protocol under
realistic traffic: clients ``offer`` uplink payloads whenever they like,
admission control answers each one with a structured verdict, and a
clock ``tick`` drains the due slice of the shared :class:`UplinkQueue`
through the single wire endpoint (``OctopusServer.ingest``, keyed on the
payload's own codebook version) into the store, then runs the
background bulk decoder. Merges and rolling codebook migrations go
through the service so they journal; ``recover`` rebuilds a killed
service from its snapshot + journal.

The population side that feeds it lives in ``repro.sim``:
``CohortEngine.run_traffic`` streams scheduler-driven rounds through the
same ``UplinkQueue`` into an ``OctopusServer``, and
``CohortEngine.run_continuous`` offers cohorts to this service.

Downstream, ``MultiTaskTrainer`` trains any number of heads from one
bulk decode of the store — see repro.server.multitask.
"""
from __future__ import annotations

import time
from typing import Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import recorder as _obs
from repro.wire import CodePayload, OctopusServer


class PendingUplink(NamedTuple):
    """A wire payload still in flight (straggler delay). Codebook version
    and label channels ride INSIDE the payload — the carrier is the
    bookkeeping."""
    arrival_round: int
    packed: CodePayload
    client_ids: np.ndarray
    sent_round: int


class UplinkQueue:
    """In-flight uplink payloads + the measured byte ledger (§2.8).

    Shared by :class:`ContinuousIngestService` and the cohort traffic
    driver (``repro.sim.cohort.CohortEngine.run_traffic``): ``send`` charges
    every payload's MEASURED ``nbytes`` to the uplink (dropped packets
    burn bytes but never land); ``deliver`` pushes everything whose
    arrival round has come through the wire endpoint.
    """

    def __init__(self):
        self._pending: List[PendingUplink] = []
        self.bytes_sent = 0
        self.bytes_delivered = 0
        self.bytes_dropped = 0
        self.bytes_rejected = 0
        self.bytes_duplicate = 0

    def send(self, packed: CodePayload, *, round: int, delay: int = 0,
             dropped: bool = False, client_ids=None) -> int:
        """Queue one payload; returns its measured nbytes."""
        n = packed.nbytes
        self.bytes_sent += n
        rec = _obs.active()
        if rec is not None:
            rec.uplink(packed, round=int(round), delay=int(delay),
                       dropped=bool(dropped),
                       n_clients=(len(client_ids)
                                  if client_ids is not None else None))
        if dropped:
            self.bytes_dropped += n
            return n
        self._pending.append(PendingUplink(
            arrival_round=int(round) + int(delay), packed=packed,
            client_ids=client_ids, sent_round=int(round)))
        if rec is not None:
            rec.metrics.set_gauge("uplink_queue_depth", len(self._pending))
        return n

    def charge(self, packed: CodePayload, *, round: int, reason: str = "",
               client_ids=None) -> int:
        """Ledger a REFUSED payload that never queues (§2.8: refusals
        still burned their uplink bytes). Returns its measured nbytes."""
        n = packed.nbytes
        self.bytes_sent += n
        self.bytes_rejected += n
        rec = _obs.active()
        if rec is not None:
            rec.uplink(packed, round=int(round), rejected=True,
                       reason=reason,
                       n_clients=(len(client_ids)
                                  if client_ids is not None else None))
        return n

    def charge_duplicate(self, packed: CodePayload, *, round: int,
                         client_ids=None) -> int:
        """Ledger a retransmit of an envelope the server already holds:
        the bytes crossed the uplink again (sent) but must never count
        delivered — exactly-once ingest is what keeps
        ``sent == delivered + dropped + rejected + duplicate + in-flight``
        an identity instead of an approximation."""
        n = packed.nbytes
        self.bytes_sent += n
        self.bytes_duplicate += n
        rec = _obs.active()
        if rec is not None:
            rec.uplink(packed, round=int(round), duplicate=True,
                       n_clients=(len(client_ids)
                                  if client_ids is not None else None))
        return n

    def reorder_tail(self) -> bool:
        """Swap the two most recently queued payloads (fault injection:
        the channel delivered them out of send order). Returns whether a
        swap happened — with fewer than two in flight there is nothing
        to reorder."""
        if len(self._pending) < 2:
            return False
        self._pending[-1], self._pending[-2] = \
            self._pending[-2], self._pending[-1]
        return True

    def deliver(self, wire: OctopusServer, round: int, *,
                results: Optional[list] = None) -> tuple:
        """Ingest every due payload; returns (nbytes, n_payloads).

        ``results`` (a list) collects one :class:`AdmissionResult` per
        delivery attempt; a payload the wire endpoint REJECTS (retired
        version, wire violation) moves its bytes to ``bytes_rejected``
        and is not counted delivered.
        """
        delivered, n_del = 0, 0
        still: List[PendingUplink] = []
        for p in self._pending:
            if p.arrival_round <= round:
                res = wire.ingest(p.packed, client_ids=p.client_ids,
                                  round=p.sent_round)
                if results is not None:
                    results.append(res)
                if res.ok:
                    delivered += p.packed.nbytes
                    n_del += 1
                else:
                    # admitted earlier, refused at the door now (e.g. its
                    # version was retired while in flight) — witness the
                    # late rejection so byte conservation stays checkable
                    self.bytes_rejected += p.packed.nbytes
                    late = _obs.active()
                    if late is not None:
                        late.metrics.inc("admission_rejected")
                        late.event("admission", round=int(round),
                                   verdict="rejected", reason=res.reason,
                                   queue_depth=len(self._pending),
                                   nbytes=p.packed.nbytes)
            else:
                still.append(p)
        self._pending = still
        self.bytes_delivered += delivered
        rec = _obs.active()
        if rec is not None:
            rec.metrics.set_gauge("uplink_queue_depth", len(self._pending))
        return delivered, n_del

    @property
    def bytes_in_flight(self) -> int:
        return sum(p.packed.nbytes for p in self._pending)

    def __len__(self) -> int:
        return len(self._pending)


class BulkDecodePolicy(NamedTuple):
    """When the background bulk decoder fires and how much it batches.

    The PR-7 flight recorder measured ``decode_amortization = 1.32``
    records per dispatch for the round-driven runtime; this grows that
    seed into a tunable policy: every ``interval_ticks`` service ticks,
    if at least ``min_batch`` freshly-stored records are waiting, decode
    up to ``max_batch`` of them in as few fused dispatches as their
    (version, bits) grouping allows. ``interval_ticks=0`` disables the
    background decoder (decode happens only when a trainer asks).
    """
    min_batch: int = 1
    max_batch: int = 64
    interval_ticks: int = 1


class TickStats(NamedTuple):
    """What one ``ContinuousIngestService.tick`` did."""
    tick: int
    n_offered: int           # uplinks offered since the previous tick
    bytes_offered: int       # their measured bytes (incl. refusals)
    n_delivered: int         # payloads ingested into the store this tick
    bytes_delivered: int
    n_decoded: int           # records background-bulk-decoded this tick
    decode_dispatches: int   # fused dispatches those decodes cost
    queue_depth: int         # in-flight payloads after this tick
    bytes_in_flight: int
    merged_version: Optional[int] = None


class ContinuousIngestService:
    """Clocked, admission-controlled ingest over ONE wire endpoint.

    The round-driven loop inverted: clients ``offer`` uplinks whenever
    they like; a clock ``tick`` drains the due slice of the queue into
    the store and runs the background bulk decoder. Admission control
    happens AT OFFER TIME:

      * wire violations (§2.5 flag, wire revision, retired/unknown
        codebook version) are rejected at the door — bytes still burn
        on the §2.8 ledger, the payload never queues;
      * a full queue (``capacity``) rejects with ``queue_full`` —
        backpressure instead of unbounded growth;
      * a queue past ``defer_depth`` admits but answers ``deferred`` —
        the client's signal to back off while the service catches up;
      * payloads packed under the src version of an open migration
        window admit as ``migrated``;
      * an ``uplink_id`` of ``(client_id, seq)`` names the envelope: a
        retransmit of a key the service already ADMITTED (client retry,
        channel duplication) answers ``duplicate`` and is never stored
        twice — exactly-once ingest over an at-least-once channel. Only
        admitted keys register, so a retry of a refused or dropped
        envelope can still land.

    Every offer gets a structured :class:`AdmissionResult`; per-verdict
    count/byte histograms live on ``.verdicts`` / ``.verdict_bytes``
    (and stream out as ``admission`` trace events).

    With ``persist`` (a ``repro.server.ServerPersistence``) the service
    is CRASH-CONSISTENT: every admitted offer / tick / merge / migration
    op is journaled append-only before it mutates state, and periodic
    snapshots capture the full durable state (store rings, ledgers,
    registry snapshots, open migration window, queue, dedup window,
    server pytree). :meth:`recover` = load latest snapshot + replay the
    journal tail through the normal code paths — the recovered store
    decodes bit-identically to an uninterrupted run over the same
    accepted records, even when the kill landed mid-migration.
    """

    def __init__(self, wire: OctopusServer, *,
                 queue: Optional[UplinkQueue] = None,
                 capacity: Optional[int] = None,
                 defer_depth: Optional[int] = None,
                 decode_policy: BulkDecodePolicy = BulkDecodePolicy(),
                 dedup_window: int = 4096,
                 persist=None):
        from collections import OrderedDict
        self.wire = wire
        self.queue = queue if queue is not None else UplinkQueue()
        self.capacity = capacity
        if defer_depth is None and capacity is not None:
            defer_depth = max(1, (3 * capacity) // 4)
        self.defer_depth = defer_depth
        self.decode_policy = decode_policy
        self.dedup_window = int(dedup_window)
        self.tick_idx = 0
        self.verdicts: Dict[str, int] = {}
        self.verdict_bytes: Dict[str, int] = {}
        self.decoded_records = 0
        self.decode_dispatches = 0
        self._pending_decode: list = []
        self._tick_offered = 0
        self._tick_bytes = 0
        self._seen: "OrderedDict" = OrderedDict()   # admitted uplink_ids
        self._replaying = False
        self._persist = persist
        if persist is not None:
            # snapshot 0: recovery always has a floor to replay from
            persist.snapshot(self)

    # ------------------------------------------------------------- offers

    def _refuse(self, verdict: str, reason: str, nbytes: int) -> None:
        """Journal a refusal so the crash-recovered ledger and verdict
        histogram match the uninterrupted run exactly (the payload
        itself never lands, so only the deltas are journaled)."""
        if self._persist is not None and not self._replaying:
            self._persist.log_refusal(verdict, reason, nbytes)

    def _replay_refusal(self, verdict: str, reason: str,
                        nbytes: int) -> None:
        """Re-apply a journaled refusal's ledger + histogram deltas."""
        q = self.queue
        q.bytes_sent += nbytes
        if verdict == "duplicate":
            q.bytes_duplicate += nbytes
        elif reason == "radio_drop":
            q.bytes_dropped += nbytes
        else:
            q.bytes_rejected += nbytes
        self.verdicts[verdict] = self.verdicts.get(verdict, 0) + 1
        self.verdict_bytes[verdict] = \
            self.verdict_bytes.get(verdict, 0) + nbytes

    def _result(self, verdict: str, reason: str, nbytes: int
                ) -> "AdmissionResult":
        from repro.wire.session import AdmissionResult
        self._tick_offered += 1
        self._tick_bytes += nbytes
        self.verdicts[verdict] = self.verdicts.get(verdict, 0) + 1
        self.verdict_bytes[verdict] = \
            self.verdict_bytes.get(verdict, 0) + nbytes
        rec = _obs.active()
        if rec is not None:
            rec.metrics.inc(f"admission_{verdict}")
            rec.event("admission", round=self.tick_idx, verdict=verdict,
                      reason=reason, queue_depth=len(self.queue),
                      nbytes=nbytes)
        return AdmissionResult(verdict, reason, nbytes, None)

    def offer(self, payload, *, client_ids=None, delay: int = 0,
              dropped: bool = False, uplink_id=None) -> "AdmissionResult":
        """One uplink at the door -> admission verdict.

        ``dropped`` models a radio-layer loss: the bytes burn (§2.8)
        but the payload never lands — verdict ``rejected/radio_drop``.
        ``uplink_id`` is the ``(client_id, seq)`` idempotency envelope:
        a key the service already admitted answers ``duplicate``
        (bytes to the duplicate ledger bucket, nothing stored).
        Rejections (wire violations, full queue) are ledgered via
        ``UplinkQueue.charge``; admitted payloads queue via ``send``
        and land at the ``tick`` whose clock reaches their delay.
        """
        p = self.wire._coerce(payload)
        if dropped:
            self.queue.send(p, round=self.tick_idx, delay=int(delay),
                            dropped=True, client_ids=client_ids)
            self._refuse("rejected", "radio_drop", p.nbytes)
            return self._result("rejected", "radio_drop", p.nbytes)
        key = None if uplink_id is None else \
            (int(uplink_id[0]), int(uplink_id[1]))
        if key is not None and key in self._seen:
            self.queue.charge_duplicate(p, round=self.tick_idx,
                                        client_ids=client_ids)
            self._refuse("duplicate", "dedup_window", p.nbytes)
            return self._result("duplicate", "dedup_window", p.nbytes)
        verdict, reason = self.wire.precheck(p)
        if verdict == "rejected":
            self.queue.charge(p, round=self.tick_idx, reason=reason,
                              client_ids=client_ids)
            self._refuse(verdict, reason, p.nbytes)
            return self._result(verdict, reason, p.nbytes)
        if self.capacity is not None and len(self.queue) >= self.capacity:
            self.queue.charge(p, round=self.tick_idx, reason="queue_full",
                              client_ids=client_ids)
            self._refuse("rejected", "queue_full", p.nbytes)
            return self._result("rejected", "queue_full", p.nbytes)
        if key is not None:
            self._seen[key] = True
            while len(self._seen) > self.dedup_window:
                self._seen.popitem(last=False)
        if self._persist is not None and not self._replaying:
            self._persist.log_offer(p, client_ids=client_ids,
                                    delay=int(delay), uplink_id=key)
        self.queue.send(p, round=self.tick_idx, delay=int(delay),
                        client_ids=client_ids)
        if verdict == "accepted" and self.defer_depth is not None \
                and len(self.queue) > self.defer_depth:
            verdict, reason = "deferred", "queue_pressure"
        return self._result(verdict, reason, p.nbytes)

    # -------------------------------------------------------------- clock

    def tick(self, *, merged_version: Optional[int] = None,
             extra_fields: Optional[Dict] = None,
             emit_event: bool = True) -> TickStats:
        """Advance the service clock one step: deliver every due payload
        into the store, then (under ``decode_policy``) bulk-decode a
        batch of freshly-stored records in the background."""
        rec = _obs.active()
        t0 = time.perf_counter() if rec is not None else 0.0
        if self._persist is not None and not self._replaying:
            self._persist.log_tick()
        results: list = []
        delivered, n_del = self.queue.deliver(self.wire, self.tick_idx,
                                              results=results)
        for res in results:
            if res.ok and res.record is not None:
                self._pending_decode.append(res.record)

        n_decoded, n_disp = 0, 0
        pol = self.decode_policy
        if pol.interval_ticks and \
                (self.tick_idx + 1) % pol.interval_ticks == 0 and \
                len(self._pending_decode) >= pol.min_batch:
            batch = self._pending_decode[:pol.max_batch]
            self._pending_decode = self._pending_decode[pol.max_batch:]
            n_decoded, n_disp = self._bulk_decode(batch)

        stats = TickStats(
            tick=self.tick_idx, n_offered=self._tick_offered,
            bytes_offered=self._tick_bytes, n_delivered=n_del,
            bytes_delivered=delivered, n_decoded=n_decoded,
            decode_dispatches=n_disp, queue_depth=len(self.queue),
            bytes_in_flight=self.queue.bytes_in_flight,
            merged_version=merged_version)
        if rec is not None and emit_event:
            dur_ms = (time.perf_counter() - t0) * 1e3
            rec.event("round", round=self.tick_idx,
                      n_offered=self._tick_offered,
                      bytes_sent=self._tick_bytes,
                      bytes_delivered=delivered,
                      n_delivered=n_del, n_decoded=n_decoded,
                      queue_depth=len(self.queue),
                      bytes_in_flight=self.queue.bytes_in_flight,
                      merged_version=merged_version, dur_ms=dur_ms,
                      **(extra_fields or {}))
        self._tick_offered = 0
        self._tick_bytes = 0
        self.tick_idx += 1
        if self._persist is not None and not self._replaying \
                and self._persist.snapshot_every \
                and self.tick_idx % self._persist.snapshot_every == 0:
            self._persist.snapshot(self)
        return stats

    def _bulk_decode(self, records) -> tuple:
        """Background decode: ONE fused dispatch per (version, bits)
        group of the batch, each against its pinned registry snapshot."""
        from .store import decode_group
        by_key: Dict[tuple, list] = {}
        for r in records:
            by_key.setdefault((r.version, r.packed.bits), []).append(r)
        rec = _obs.active()
        n_decoded = 0
        for (v, _), recs in by_key.items():
            cb = self.wire.registry.get(v)
            t0 = time.perf_counter() if rec is not None else 0.0
            blocks = decode_group(recs, self.wire.cfg, self.wire.state, cb)
            if rec is not None:
                jax.block_until_ready(blocks)
                dur_ms = (time.perf_counter() - t0) * 1e3
                rec.event("decode", version=int(v), dur_ms=dur_ms,
                          n_records=len(recs),
                          n_samples=int(sum(b.shape[0] for b in blocks)))
            n_decoded += len(recs)
        self.decoded_records += n_decoded
        self.decode_dispatches += len(by_key)
        return n_decoded, len(by_key)

    def drain(self, max_ticks: int = 1000) -> List[TickStats]:
        """Tick until the queue is empty (or ``max_ticks``), then let
        the background decoder catch up. A tail batch the policy would
        never take on its own (fewer than ``min_batch`` records waiting,
        or the background decoder disabled) is flushed directly — a
        journaled service must not spin ``max_ticks`` of empty clock
        (and journal entries) over an undrainable remainder."""
        out = []
        while len(self.queue) and len(out) < max_ticks:
            out.append(self.tick())
        pol = self.decode_policy
        while self._pending_decode and len(out) < max_ticks:
            if not pol.interval_ticks \
                    or len(self._pending_decode) < pol.min_batch:
                batch = self._pending_decode[:pol.max_batch]
                self._pending_decode = self._pending_decode[pol.max_batch:]
                self._bulk_decode(batch)
            else:
                out.append(self.tick())
        return out

    # ------------------------------------------- journaled server-side ops

    def merge_stats(self, stats) -> int:
        """Step 5 merge through the service door (journaled): delegates
        to ``OctopusServer.merge_stats`` and journals the POST-merge
        dictionary + version, so replay re-registers the bit-identical
        snapshot without the client statistics."""
        version = self.wire.merge_stats(stats)
        if self._persist is not None and not self._replaying:
            self._persist.log_merge(
                self.wire.state.params["codebook"], version)
        return version

    def begin_migration(self, *, src: Optional[int] = None,
                        dst: Optional[int] = None, policy: str = "keep"):
        """Journaled ``OctopusServer.begin_migration`` — a kill with the
        window open replays back INTO the open window."""
        win = self.wire.begin_migration(src=src, dst=dst, policy=policy)
        if self._persist is not None and not self._replaying:
            self._persist.log_migration("begin", src=win.src, dst=win.dst,
                                        policy=win.policy)
        return win

    def complete_migration(self):
        """Journaled ``OctopusServer.complete_migration``."""
        progress = self.wire.complete_migration()
        if self._persist is not None and not self._replaying:
            self._persist.log_migration("complete")
        return progress

    def _replay_merge(self, codebook, version: int) -> None:
        """Re-apply a journaled merge: adopt the journaled post-merge
        dictionary (``server_merge_stats`` replaces ONLY the codebook
        param) and re-register it as the journaled version."""
        self.wire.state = self.wire.state._replace(
            params={**self.wire.state.params,
                    "codebook": jnp.asarray(codebook)})
        got = self.wire.registry.register(self.wire.state.params["codebook"])
        if got != int(version):
            raise RuntimeError(
                f"journal replay diverged: merge registered v{got}, "
                f"journal says v{version}")

    # ------------------------------------------------------------ recovery

    @classmethod
    def recover(cls, persist, cfg, state_like, *, shard_fn=None,
                **service_kw) -> "ContinuousIngestService":
        """Rebuild a crashed service: latest snapshot + journal replay.

        ``persist`` is a ``ServerPersistence`` rooted at the crashed
        service's directory (or the directory path itself); ``cfg`` /
        ``state_like`` are the deployment's DVQAEConfig and a template
        ``ServerState`` of the right pytree structure (e.g. a fresh
        ``octopus.server_init``) — checkpoint restore needs the shapes.
        Journal entries after the snapshot's high-water mark replay
        through the NORMAL offer/tick/merge/migration paths with the
        flight recorder detached (the pre-crash run already emitted
        those events); one ``recovery`` event summarizes the drill.
        Extra ``service_kw`` (capacity, defer_depth, decode_policy, ...)
        must match the crashed service's construction.
        """
        from repro.server.persist import ServerPersistence
        from repro.wire.session import OctopusServer as _Server
        if not isinstance(persist, ServerPersistence):
            persist = ServerPersistence(persist, resume=True)
        t0 = time.perf_counter()
        snap = persist.load_snapshot(cfg, state_like, shard_fn=shard_fn)
        wire = _Server(snap["state"], cfg, store=snap["store"],
                       registry=snap["registry"])
        service = cls(wire, **service_kw)
        service.queue = snap["queue"]
        service.tick_idx = snap["tick_idx"]
        service.verdicts = snap["verdicts"]
        service.verdict_bytes = snap["verdict_bytes"]
        service.decoded_records = snap["decoded_records"]
        service.decode_dispatches = snap["decode_dispatches"]
        service._seen = snap["seen"]

        # replay the journal tail with the recorder DETACHED: these
        # mutations already streamed their events before the crash
        rec = _obs.active()
        if rec is not None:
            _obs.uninstall()
        service._replaying = True
        n_replayed = 0
        try:
            for entry in persist.journal.entries(start=snap["journal_pos"]):
                kind = entry["kind"]
                if kind == "offer":
                    service.offer(persist.decode_offer_payload(entry),
                                  client_ids=entry.get("client_ids"),
                                  delay=entry.get("delay", 0),
                                  uplink_id=entry.get("uplink_id"))
                elif kind == "refusal":
                    service._replay_refusal(entry["verdict"],
                                            entry["reason"],
                                            entry["nbytes"])
                elif kind == "tick":
                    service.tick(emit_event=False)
                elif kind == "merge":
                    service._replay_merge(
                        persist.decode_merge_codebook(entry),
                        entry["version"])
                elif kind == "migration":
                    if entry["phase"] == "begin":
                        service.wire.begin_migration(
                            src=entry["src"], dst=entry["dst"],
                            policy=entry["policy"])
                    else:
                        service.wire.complete_migration()
                n_replayed += 1
        finally:
            service._replaying = False
            if rec is not None:
                _obs.install(rec)
        service._persist = persist
        dur_ms = (time.perf_counter() - t0) * 1e3
        rec = _obs.active()
        if rec is not None:
            rec.metrics.inc("recoveries")
            rec.event("recovery", tick=service.tick_idx,
                      snapshot_tick=snap["snapshot_tick"],
                      n_replayed=n_replayed, dur_ms=dur_ms,
                      queue_depth=len(service.queue),
                      store_records=len(service.wire.store))
        return service

    # ----------------------------------------------------------- metrics

    @property
    def decode_amortization(self) -> float:
        """Records decoded per fused dispatch (higher = better batching)."""
        return self.decoded_records / max(self.decode_dispatches, 1)

    @property
    def n_rejected(self) -> int:
        return self.verdicts.get("rejected", 0)

    @property
    def n_deferred(self) -> int:
        return self.verdicts.get("deferred", 0)
