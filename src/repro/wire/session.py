"""Session facades over the wire protocol: one client entry, one server
entry.

After PRs 1-4 the client↔server interface was a function zoo
(``client_transmit`` / ``client_round`` / ``client_round_fused`` /
``client_finetune_encode`` on one side; ``gather_codes`` /
``unpack_transmission`` / hand-wired CodeStore+Registry on the other).
These two classes subsume it:

  * :class:`OctopusClient` — ``round(batch)`` is THE uplink: Steps 2-5
    through the fused Pallas encode path (ONE encoder pass feeding ONE
    ``ops.encode_codes`` dispatch that quantizes, bit-packs and
    accumulates the EMA statistics on-chip), returning a
    :class:`CodePayload`. Policy flags pick the protocol profile —
    ``finetune=0`` skips Step 2, ``refresh=False`` skips Step 5;
    ``transmit(batch)`` is the encode-only profile (the old
    ``client_transmit``).
  * :class:`OctopusServer` — ``ingest(payload)`` / ``features()`` is THE
    downlink: payloads land in a versioned CodeStore keyed on the
    payload's OWN codebook version and decode against the registry
    snapshot they were packed under. ``ingest`` returns a structured
    :class:`AdmissionResult` verdict (accepted / migrated / deferred /
    rejected) instead of raising — payloads that are not marked
    ``privatized``, speak a different wire revision, or name a retired
    codebook version are REJECTED with a reason, and their measured
    bytes stay on the §2.8 ledger. Rolling ``v_n -> v_{n+1}`` codebook
    upgrades run through ``begin_migration`` / ``complete_migration``.

The pure, jittable round core is :func:`round_words` — bit-identical to
the PR-4 ``client_round_fused`` tail (same calls, same dispatch count);
``SimEngine`` remains the batched population driver for the same wire.
"""
from __future__ import annotations

import time
import zlib
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import octopus as OC
from repro.core.dvqae import DVQAEConfig
from repro.obs import recorder as _obs

from .payload import (SUPPORTED_WIRE_VERSIONS, WIRE_VERSION, CodePayload,
                      as_payload)

#: admission verdicts an ingest path can return (§2.8: ALL of them keep
#: the payload's measured bytes on the ledger, accepted or not)
ADMISSION_VERDICTS = ("accepted", "migrated", "deferred", "rejected",
                      "duplicate")

#: rejection reasons worth retrying: the condition is transient (load or
#: channel noise), so the SAME envelope re-sent later can land. The
#: other reasons (wire_revision, unprivatized, retired/unknown version)
#: are protocol facts a retransmit cannot fix.
TRANSIENT_REASONS = ("queue_full", "radio_drop", "corrupt")


class RetryPolicy(NamedTuple):
    """Capped exponential backoff for transient uplink failures.

    Attempt ``a`` waits ``min(base_ticks * 2**a, cap_ticks)`` service
    ticks plus a deterministic jitter in ``[0, jitter_ticks]`` hashed
    from (salt, attempt) — retries de-synchronize across clients without
    consuming anybody's PRNG stream (toggling retry must not perturb
    population or traffic draws).
    """
    max_attempts: int = 4
    base_ticks: int = 1
    cap_ticks: int = 8
    jitter_ticks: int = 1

    def backoff(self, attempt: int, *, salt="") -> int:
        wait = min(self.base_ticks * (2 ** int(attempt)), self.cap_ticks)
        if self.jitter_ticks:
            h = zlib.crc32(f"retry|{salt}|{int(attempt)}".encode())
            wait += h % (self.jitter_ticks + 1)
        return int(wait)

    def retryable(self, result: "AdmissionResult") -> bool:
        """deferred and transient rejections retry; accepted / migrated /
        duplicate (the server already holds this envelope) stop."""
        return (result.verdict == "deferred"
                or (result.verdict == "rejected"
                    and result.reason in TRANSIENT_REASONS))


class AdmissionResult(NamedTuple):
    """Structured verdict for one uplink payload at the server door.

    ``verdict``:
      accepted — stored (or queued) on the current codebook version
      migrated — stored, but packed under the src version of an OPEN
                 migration window (will be kept/retired/re-encoded when
                 the window closes)
      deferred — queued under backpressure; will be decoded, later
      rejected — refused (``reason`` says why); bytes still ledgered
      duplicate — this ``(client_id, seq)`` envelope was already
                 admitted; the retransmit is acknowledged but NOT
                 stored again (exactly-once ingest)
    ``nbytes`` is the payload's measured wire size; ``record`` is the
    StoreRecord for verdicts that stored the payload, else None.
    """
    verdict: str
    reason: str = ""
    nbytes: int = 0
    record: Optional[object] = None

    @property
    def ok(self) -> bool:
        return self.verdict != "rejected"


# --------------------------------------------------------- pure round core

def _round_core(client: OC.ClientState, cfg: DVQAEConfig, batch, *,
                lr: float = 1e-4, gamma: float = 0.99,
                n_local_steps: int = 1, refresh: bool = True):
    """Steps 2-5 with the fused uplink tail -> (client, z, words).

    Exactly the ``client_round_fused`` computation: ``n_local_steps`` of
    frozen-codebook fine-tuning, ONE encoder pass, ONE
    ``ops.encode_codes`` dispatch (quantize + pack + EMA stats on-chip),
    optional Step 5 refresh from the precomputed statistics. Neither the
    (N, K) distance matrix nor the int32 index tensor ever materializes.
    """
    from repro.kernels.ops import encode_codes
    client, z = OC.client_finetune_encode(client, cfg, batch, lr=lr,
                                          n_local_steps=n_local_steps)
    zf = z.reshape(1, -1, z.shape[-1])
    words, counts, sums = encode_codes(
        zf, client.params["codebook"][None], bits=OC.transmit_bits(cfg),
        n_groups=cfg.n_groups, n_slices=cfg.n_slices)
    if refresh:
        client = OC.client_codebook_refresh(client, cfg, None, gamma=gamma,
                                            stats=(counts[0], sums[0]))
    return client, z, words


def round_words(client: OC.ClientState, cfg: DVQAEConfig, batch, *,
                lr: float = 1e-4, gamma: float = 0.99,
                n_local_steps: int = 1, refresh: bool = True
                ) -> Tuple[OC.ClientState, jax.Array]:
    """Pure jittable round: (client, batch) -> (client, uint32 words).

    The words are exactly ``pack_codes(indices, transmit_bits(cfg))`` for
    the round's indices — wrap in ``jax.jit`` (or drive populations via
    ``SimEngine``) and build the :class:`CodePayload` outside the trace.
    """
    client, _, words = _round_core(client, cfg, batch, lr=lr, gamma=gamma,
                                   n_local_steps=n_local_steps,
                                   refresh=refresh)
    return client, words


def index_shape(cfg: DVQAEConfig, z_shape) -> Tuple[int, ...]:
    """Transmitted index shape for latents of shape (..., M): GSVQ sends
    one group index per slice per position."""
    base = tuple(int(d) for d in z_shape[:-1])
    if cfg.n_groups > 1 or cfg.n_slices > 1:
        return base + (cfg.n_slices,)
    return base


def fused_round(client: OC.ClientState, cfg: DVQAEConfig, batch, *,
                lr: float = 1e-4, gamma: float = 0.99,
                n_local_steps: int = 1, refresh: bool = True,
                version: int = 0, labels=None
                ) -> Tuple[OC.ClientState, CodePayload]:
    """One client round -> (client, CodePayload). The payload carries the
    wire's (C, B, ...) leading layout with C == 1 — one record stream,
    ready for ``OctopusServer.ingest`` / ``CodeStore.add``."""
    client, z, words = _round_core(client, cfg, batch, lr=lr, gamma=gamma,
                                   n_local_steps=n_local_steps,
                                   refresh=refresh)
    shape = (1,) + index_shape(cfg, z.shape)
    return client, CodePayload.from_words(
        words, bits=OC.transmit_bits(cfg), shape=shape, n_records=1,
        version=version, labels=labels, n_samples=int(z.shape[0]),
        privatized=True)


# ----------------------------------------------------------------- client

class OctopusClient:
    """One client device's session: local DVQ-AE state + uplink policy.

    ``server`` is an :class:`OctopusServer` (deploys from its current
    state and codebook version) or a bare ``octopus.ServerState``.
    """

    def __init__(self, server, cfg: Optional[DVQAEConfig] = None, *,
                 lr: float = 1e-4, gamma: float = 0.99,
                 n_local_steps: int = 1, client_id: int = 0):
        if isinstance(server, OctopusServer):
            cfg = cfg or server.cfg
            state, version = server.state, server.version
        else:
            if cfg is None:
                raise ValueError("OctopusClient(ServerState, ...) needs an "
                                 "explicit cfg")
            state, version = server, 0
        self.cfg = cfg
        self.lr = lr
        self.gamma = gamma
        self.n_local_steps = n_local_steps
        self.client_id = int(client_id)
        self.state = OC.client_init(state)
        self.version = int(version)
        self._seq = 0                    # next uplink envelope sequence no.

    # -------------------------------------------------------------- steps

    @property
    def codebook(self) -> jax.Array:
        return self.state.params["codebook"]

    def finetune(self, batch, *, steps: int = 1, lr: Optional[float] = None
                 ) -> None:
        """Explicit Step 2: frozen-codebook local fine-tuning."""
        opt = None
        for _ in range(steps):
            self.state, opt, _ = OC.client_finetune_step(
                self.state, self.cfg, batch,
                lr=self.lr if lr is None else lr, opt=opt)

    def round(self, batch, *, labels=None, finetune: Optional[int] = None,
              refresh: bool = True) -> CodePayload:
        """THE uplink entry: Steps 2-5 through the fused encode path.

        ``finetune`` overrides the session's ``n_local_steps`` for this
        round (0 skips Step 2); ``refresh=False`` skips the Step 5 EMA
        refresh. Returns the round's :class:`CodePayload`, stamped with
        the codebook version this client deployed from.
        """
        n_local = self.n_local_steps if finetune is None else int(finetune)
        rec = _obs.active()
        t0 = time.perf_counter() if rec is not None else 0.0
        self.state, payload = fused_round(
            self.state, self.cfg, batch, lr=self.lr, gamma=self.gamma,
            n_local_steps=n_local, refresh=refresh, version=self.version,
            labels=labels)
        if rec is not None:
            jax.block_until_ready(payload.payload)
            rec.event("encode", dur_ms=(time.perf_counter() - t0) * 1e3,
                      client_id=self.client_id, n_local_steps=n_local,
                      refresh=bool(refresh), **_obs.payload_meta(payload))
            rec.uplink(payload, client_id=self.client_id)
        return payload

    def transmit(self, batch, *, labels=None) -> CodePayload:
        """Encode-only uplink (Steps 3-4): no fine-tuning, no refresh —
        the old ``client_transmit``, minus the materialized index tensor."""
        return self.round(batch, labels=labels, finetune=0, refresh=False)

    # ---------------------------------------------------- exactly-once send

    def next_seq(self) -> int:
        """Mint the next envelope sequence number: ``(client_id, seq)``
        is the idempotency key the server dedups retransmits on."""
        seq, self._seq = self._seq, self._seq + 1
        return seq

    def send(self, target, payload: CodePayload, *,
             retry: Optional[RetryPolicy] = None,
             clock=None) -> AdmissionResult:
        """Offer ONE payload under a fresh ``(client_id, seq)`` envelope,
        retrying transient verdicts with capped exponential backoff.

        ``target`` is anything with the continuous ``offer`` door (a
        ``ContinuousIngestService`` or a ``FaultyChannel`` in front of
        one). Between attempts the client waits ``retry.backoff`` ticks
        by calling ``clock()`` (default: ``target.tick``) — the envelope
        key stays FIXED across attempts, so a retransmit of a payload
        the server already admitted comes back ``duplicate`` and is
        never double-counted.
        """
        seq = self.next_seq()
        step = clock if clock is not None else getattr(target, "tick", None)
        rec = _obs.active()
        attempt = 0
        while True:
            res = target.offer(payload, client_ids=[self.client_id],
                               uplink_id=(self.client_id, seq))
            if (retry is None or not retry.retryable(res)
                    or attempt >= retry.max_attempts):
                return res
            wait = retry.backoff(attempt,
                                 salt=f"{self.client_id}.{seq}")
            if rec is not None:
                rec.metrics.inc("retries")
                rec.event("retry", client_id=self.client_id, seq=seq,
                          attempt=attempt, wait_ticks=wait,
                          verdict=res.verdict, reason=res.reason)
            if step is not None:
                for _ in range(wait):
                    step()
            attempt += 1

    def uplink(self, target, batch, *, labels=None,
               retry: Optional[RetryPolicy] = None,
               clock=None) -> AdmissionResult:
        """``round`` + exactly-once ``send`` in one call: encode the
        batch ONCE, then (re)transmit the same payload under one
        idempotency key until the server holds it or retries exhaust."""
        return self.send(target, self.round(batch, labels=labels),
                         retry=retry, clock=clock)

    def sync(self, server: "OctopusServer") -> None:
        """Adopt the server's latest merged dictionary (Step 5 tail on
        the client side) and its codebook version; the local EMA restarts
        from the adopted atoms, fine-tuned encoder/decoder stay."""
        from repro.core.ema import init_ema
        cb = server.registry.current
        self.state = OC.ClientState(
            params={**self.state.params, "codebook": cb},
            ema=init_ema(cb), step=self.state.step)
        self.version = server.version


# ----------------------------------------------------------------- server

class OctopusServer:
    """Server session: versioned registry + code store behind ONE door.

    ``ingest`` keys every payload on its own ``version`` field (the
    per-delivery-group bookkeeping structs of the async runtime collapse
    into the carrier); ``features`` bulk-decodes version-correctly.
    """

    def __init__(self, server, cfg: Optional[DVQAEConfig] = None, *,
                 store=None, registry=None, require_privatized: bool = True):
        from repro.server.registry import CodebookRegistry
        from repro.server.store import CodeStore
        if not isinstance(server, OC.ServerState):
            raise TypeError("OctopusServer wraps an octopus.ServerState; "
                            "build one with octopus.server_init(key, cfg)")
        if cfg is None:
            raise ValueError("OctopusServer needs the DVQAEConfig")
        self.cfg = cfg
        self.state = server
        self.registry = registry if registry is not None else \
            CodebookRegistry(server.params["codebook"])
        self.store = store if store is not None else CodeStore(cfg)
        self.require_privatized = require_privatized

    @classmethod
    def init(cls, key, cfg: DVQAEConfig, *, lr: float = 1e-3, **kw
             ) -> "OctopusServer":
        return cls(OC.server_init(key, cfg, lr=lr), cfg, **kw)

    # ------------------------------------------------------------ protocol

    @property
    def version(self) -> int:
        """Current (latest merged) codebook version."""
        return self.registry.latest

    def pretrain(self, key, x, *, steps: int, batch: int = 32,
                 lr: float = 1e-3):
        """Step 1: ATD pretraining of the global DVQ-AE. Re-pins the
        pretrained dictionary as the current registry snapshot — only
        legal before any payload landed, or already-stored codes would
        silently decode against a dictionary they were not packed under.
        """
        if len(self.store):
            raise RuntimeError(
                f"pretrain would move codebook version "
                f"{self.registry.latest} under {len(self.store)} stored "
                f"payload(s); pretrain before ingesting (Step 1 precedes "
                f"Step 4)")
        self.state, out = OC.server_pretrain(key, self.state, self.cfg, x,
                                             steps=steps, batch=batch, lr=lr)
        self.registry.pin_current(self.state.params["codebook"])
        return out

    def deploy(self, **client_kw) -> OctopusClient:
        """Step 2: hand a client a session on the current global model."""
        return OctopusClient(self, **client_kw)

    def _coerce(self, payload) -> CodePayload:
        """Any carrier -> a CodePayload in the wire's (C, B, ...) leading
        layout. Legacy packed Transmissions ((B, T[, n_c]) indices with
        per-sample labels) are lifted to a single-client record."""
        p = as_payload(payload)
        if p is None:
            raise TypeError(f"the wire endpoint wants a CodePayload (or a "
                            f"packed legacy carrier), got "
                            f"{type(payload).__name__}")
        if hasattr(payload, "indices"):
            # the checksum covers the shape — restamp after the lift
            p = p._replace(shape=(1,) + p.shape).stamped()
        return p

    def precheck(self, p: CodePayload) -> Tuple[str, str]:
        """Wire-invariant admission check -> (verdict, reason), without
        touching the store. Rejections: unknown wire revision, missing
        §2.5 privatized flag, retired or never-registered codebook
        version, or a failed integrity check (short word stream, CRC
        mismatch) -> ``corrupt``. A payload packed under the src version
        of an OPEN migration window admits as ``migrated``."""
        if p.wire not in SUPPORTED_WIRE_VERSIONS:
            return "rejected", "wire_revision"
        if self.require_privatized and not p.privatized:
            return "rejected", "unprivatized"
        if self.registry.is_retired(p.version):
            return "rejected", "retired_version"
        if p.version not in self.registry:
            return "rejected", "unknown_version"
        if not p.verify():
            return "rejected", "corrupt"
        win = self.registry.migration
        if win is not None and int(p.version) == win.src:
            return "migrated", "migration_window"
        return "accepted", ""

    def ingest(self, payload, *, client_ids=None, round: int = 0
               ) -> AdmissionResult:
        """THE downlink entry: one payload into the versioned store.

        Coerces legacy carriers (packed ``Transmission``) — a carrier
        that is not a payload at all still raises ``TypeError`` — then
        runs :meth:`precheck` and returns a structured
        :class:`AdmissionResult` instead of raising on wire violations.
        Rejected payloads do NOT enter the store, but their measured
        bytes are counted (§2.8 accounting includes refusals).
        """
        p = self._coerce(payload)
        verdict, reason = self.precheck(p)
        rec = _obs.active()
        if verdict == "rejected":
            if rec is not None:
                rec.metrics.inc("uplinks_rejected")
                rec.metrics.inc("bytes_rejected", p.nbytes)
            return AdmissionResult(verdict, reason, p.nbytes, None)
        out = self.store.add(p, client_ids=client_ids, round=round)
        if rec is not None:
            rec.metrics.inc("uplinks_ingested")
            rec.metrics.inc("bytes_ingested", p.nbytes)
            if verdict == "migrated":
                rec.metrics.inc("uplinks_migrated")
            rec.event("ingest", round=int(round), verdict=verdict,
                      **_obs.payload_meta(p))
        return AdmissionResult(verdict, reason, p.nbytes, out)

    def features(self, *, version: Optional[int] = None):
        """Bulk decode of everything ingested, each version group against
        its own registry snapshot, ONE fused dispatch per version.
        ``version`` filters to payloads packed under that version.
        Returns (features (N, ...), {task: (N,) labels})."""
        return self.store.dataset(self.state, registry=self.registry,
                                  version=version)

    def decode(self, payload) -> jax.Array:
        """Directly decode ONE payload (store bypass) against the
        snapshot it was packed under; merges the client axis. Legacy
        Transmissions are lifted to (C=1, ...) like ``ingest`` does."""
        p = self._coerce(payload)
        rec = _obs.active()
        t0 = time.perf_counter() if rec is not None else 0.0
        feats = OC.codes_to_features(None, self.cfg, p,
                                     codebook=self.registry.get(p.version))
        out = feats.reshape((-1,) + feats.shape[2:])
        if rec is not None:
            jax.block_until_ready(out)
            dur_ms = (time.perf_counter() - t0) * 1e3
            rec.event("decode", version=int(p.version), dur_ms=dur_ms,
                      n_samples=int(out.shape[0]))
        return out

    # ----------------------------------------------------------- migration

    def begin_migration(self, *, src: Optional[int] = None,
                        dst: Optional[int] = None, policy: str = "keep"):
        """Open a rolling ``src -> dst`` codebook upgrade window (defaults:
        latest-1 -> latest). While open, payloads of BOTH versions ingest
        concurrently — src-version ones get ``migrated`` verdicts."""
        win = self.registry.begin_migration(src=src, dst=dst, policy=policy)
        rec = _obs.active()
        if rec is not None:
            rec.metrics.set_gauge("migration_open", 1)
            rec.event("migration", phase="begin", src=win.src, dst=win.dst,
                      policy=win.policy)
        return win

    def migration_progress(self) -> Dict[str, int]:
        """Record/byte counts for the open window's src and dst versions —
        how much of the store still speaks the old dictionary."""
        win = self.registry.migration
        if win is None:
            raise ValueError("no migration window is open")
        by_v = self.store.stored_bytes_by_version
        recs = self.store.records
        return {
            "src": win.src, "dst": win.dst,
            "src_records": sum(1 for r in recs if r.version == win.src),
            "dst_records": sum(1 for r in recs if r.version == win.dst),
            "src_bytes": by_v.get(win.src, 0),
            "dst_bytes": by_v.get(win.dst, 0),
        }

    def complete_migration(self) -> Dict[str, int]:
        """Close the window and apply its policy to src-version records:
        ``keep`` leaves them decoding against their pinned snapshot;
        ``retire`` evicts them (bytes stay ledgered) and refuses future
        src uplinks; ``reencode`` transcodes them to the dst codebook
        before retiring src. Returns the final progress summary."""
        progress = self.migration_progress()
        win = self.registry.close_migration()
        n_reencoded = 0
        if win.policy in ("retire", "reencode"):
            gone = self.store.retire_version(win.src)
            if win.policy == "reencode":
                for r in gone:
                    p = self._reencode_payload(r.packed, win.dst)
                    self.store.add(p, client_ids=r.client_ids,
                                   round=r.round, labels=r.labels)
                    n_reencoded += 1
            self.registry.retire(win.src)
        progress["n_reencoded"] = n_reencoded
        rec = _obs.active()
        if rec is not None:
            rec.metrics.set_gauge("migration_open", 0)
            rec.event("migration", phase="complete", src=win.src,
                      dst=win.dst, policy=win.policy,
                      src_records=progress["src_records"],
                      src_bytes=progress["src_bytes"],
                      n_reencoded=n_reencoded)
        return progress

    def _reencode_payload(self, packed: CodePayload, dst: int
                          ) -> CodePayload:
        """Transcode one payload to the ``dst`` codebook: decode against
        the snapshot it was packed under, re-quantize each feature to its
        nearest dst atom, re-pack under ``dst``. Plain-VQ only — a GSVQ
        index names a (group, slice) product atom, so transcoding it
        needs the full encoder path, not a nearest-atom lookup."""
        if self.cfg.n_groups > 1 or self.cfg.n_slices > 1:
            raise ValueError("reencode migration supports plain VQ only "
                             f"(cfg has n_groups={self.cfg.n_groups}, "
                             f"n_slices={self.cfg.n_slices})")
        feats = OC.codes_to_features(
            None, self.cfg, packed,
            codebook=self.registry.get(packed.version))  # (C, B, ..., M)
        cb = self.registry.get(dst)                      # (K, M)
        d = jnp.sum((feats[..., None, :] - cb) ** 2, axis=-1)
        idx = jnp.argmin(d, axis=-1).astype(jnp.int32)
        return CodePayload.pack(idx, bits=packed.bits, version=int(dst),
                                privatized=True)

    # --------------------------------------------------------- Step 5 tail

    def merge(self, client_codebooks, client_counts, *, client_versions=None,
              staleness_decay: float = 1.0) -> int:
        """Staleness-weighted Step 5 merge; registers and returns the new
        codebook version."""
        self.state, version = self.registry.merge(
            self.state, client_codebooks, client_counts,
            client_versions=client_versions,
            staleness_decay=staleness_decay)
        rec = _obs.active()
        if rec is not None:
            rec.metrics.inc("merges")
            rec.event("merge", version=int(version),
                      n_clients=int(len(client_counts)))
        return version

    def merge_clients(self, clients: OC.ClientState, **kw) -> int:
        """Merge a stacked population (e.g. ``SimEngine`` client state)."""
        return self.merge(clients.params["codebook"], clients.ema.counts,
                          **kw)

    def merge_stats(self, stats) -> int:
        """Step 5 tail from ASSOCIATIVE cohort statistics
        (``repro.core.ema.MergeStats``): the cohort engine streams a
        round cohort-by-cohort and folds each cohort's fixed-point
        contribution into one accumulator; this finishes the merge and
        registers the new dictionary version. Bit-identical for any
        cohort partition/order of the same client set. The merge and the
        registration run inside the span ``octopus/server/merge``, whose
        ``version`` is the version merged from."""
        with _obs.span("server/merge", version=int(self.version)):
            self.state = OC.server_merge_stats(self.state, stats)
            version = self.registry.register(self.state.params["codebook"])
        rec = _obs.active()
        if rec is not None:
            rec.metrics.inc("merges")
            rec.event("merge", version=int(version), source="stats")
        return version
