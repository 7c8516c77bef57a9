"""Open-loop ingest: single-client uplinks offered to the server's
continuous ingest service at a fixed rate.

Arrivals are a Poisson process of ``rate`` per second over the window,
conditioned on its count: ``rate * seconds`` offers whose gaps are one
fixed set of exponential draws, scaled to fill the window, which each
seed puts in another order. So every seed offers the same uplinks with
the same gaps, and only the order of bursts and lulls changes. Each uplink is one client's record of
``samples_per_client`` images, picked from a pool of ``payload_pool``
payloads that set-up makes through the real client path (one
``SimEngine`` round from the seeded weights), under a fresh
``(client_id, seq)`` envelope; client ids are Zipf-skewed over the
population. The loop offers every uplink that is due, then ticks the
service once (deliver into the ``ShardedCodeStore``, background bulk
decode under the default ``BulkDecodePolicy``).

An uplink's latency runs from its scheduled send time to the moment its
record's decode has finished on the device. The service drops the
blocks it decodes, so the driver wraps ``repro.server.store.
decode_group`` (the function the service's decoder calls) to see them,
and waits for them after each tick. After the window the loop keeps
ticking, ``drain_s`` at most, until every admitted record is decoded.
"""
from __future__ import annotations

import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness.checks import Compared, gather_diff, ledger_imbalance
from bench.harness.images import make_images, seed_key
from bench.harness import work
from bench.harness.program import program_config, server_state

class Driver:
    def __init__(self, cell, seed: int, limits: dict):
        self.cell, self.seed, self.limits = cell, int(seed), limits
        c, m = cell.config, cell.mix
        self.model, self.client = c["model"], c["client"]
        self.image = c["input"]["image"]
        self.samples = c["samples_per_client"]
        self.population = c["population"]["clients"]
        self.mix = m
        self.rng = np.random.default_rng(self.seed)
        self.captured = []

    # ----------------------------------------------------------- set-up

    def setup(self):
        import repro.server.store as store_mod
        from repro.sim import SimEngine
        from repro.wire import CodePayload
        m, model = self.mix, self.model
        self.cfg = program_config(model)
        self.params = jax.jit(partial(self.cell.reference.init_params,
                                      model=model))(seed_key(self.seed, 1))
        t0 = time.perf_counter()
        n = m["payload_pool"]
        x = make_images(seed_key(self.seed, 2), n=n * self.samples,
                        size=self.image, channels=model["in_channels"],
                        identities=m["identities"])
        eng = SimEngine(self.cfg, lr=self.client["lr"],
                        gamma=self.client["gamma"],
                        n_local_steps=1)
        _, out = eng.round(eng.init_clients(server_state(self.params), n),
                           x.reshape((n, self.samples) + x.shape[1:]))
        rows = out.payload.shape[0] // n
        self.payloads = [CodePayload.from_words(
            out.payload[i * rows:(i + 1) * rows], bits=out.bits,
            shape=(1,) + tuple(out.shape[1:]), n_records=1, version=0)
            for i in range(n)]
        self.words = [np.asarray(p.payload) for p in self.payloads]
        t1 = time.perf_counter()

        # the service drops the blocks it decodes: keep them to time and
        # check the decodes (the blocks are returned unchanged)
        original = store_mod.decode_group

        def seen(recs, *a, **kw):
            blocks = original(recs, *a, **kw)
            self.captured.append((recs, blocks))
            return blocks
        store_mod.decode_group = seen
        self._restore = lambda: setattr(store_mod, "decode_group", original)

        # warm every decode batch size the policy can take, 1..max_batch
        warm = self.make_service()
        for k in range(1, warm.decode_policy.max_batch + 1):
            for j in range(k):
                warm.offer(self.payloads[j % n], client_ids=[j],
                           uplink_id=(j, k))
            warm.tick()
            jax.block_until_ready([b for _, b in self.captured])
            self.captured.clear()
        self.service = self.make_service()
        print(f"set-up: weights and payloads {t1 - t0:.3f} s, decode "
              f"warm-up {time.perf_counter() - t1:.3f} s", file=sys.stderr)

    def make_service(self):
        """A fresh ingest service over a fresh store, on the seeded
        weights (codebook version 0)."""
        from repro.server import (BulkDecodePolicy, ContinuousIngestService,
                                  ShardedCodeStore)
        from repro.wire import OctopusServer
        srv = OctopusServer(server_state(self.params), self.cfg,
                            store=ShardedCodeStore(
                                self.cfg, n_shards=self.mix["n_shards"]))
        return ContinuousIngestService(
            srv, decode_policy=BulkDecodePolicy(*self.mix["decode_policy"]))

    # ----------------------------------------------------------- window

    def _schedule(self, seconds: float):
        """Arrivals, envelopes and payload choice, all from the seed."""
        m, n = self.mix, len(self.payloads)
        N = self.n_offers = int(round(m["rate"] * seconds))
        zipf = 1.0 / np.arange(1, self.population + 1) ** m["zipf_s"]
        self.cids = self.rng.choice(self.population, N, p=zipf / zipf.sum())
        self.pick = self.rng.integers(n, size=N)
        seq, self.seqs = {}, np.zeros(N, np.int64)
        for i, c in enumerate(self.cids):
            self.seqs[i] = seq.get(c, 0)
            seq[c] = self.seqs[i] + 1
        self.sample = set(self.rng.choice(
            N, min(m["check_records"], N), replace=False).tolist())
        gaps = np.random.default_rng(N).exponential(1.0, N + 1)
        gaps *= seconds / gaps.sum()
        return np.cumsum(self.rng.permutation(gaps))[:N]

    def window(self, seconds: float, span):
        """Offer each uplink when due, tick once per loop pass."""
        sched = self._schedule(seconds)
        svc, N = self.service, self.n_offers
        done = np.full(N, np.inf)
        self.offer_s = np.zeros(N)
        accepted = []                       # offer indices, FIFO
        decoded = 0
        self.order_mismatch = 0
        self.kept = {}
        self.captured.clear()
        i = 0
        t0 = time.perf_counter()
        close = t0 + seconds
        while True:
            now = time.perf_counter()
            if i >= N and decoded >= len(accepted):
                break
            if now > close + self.mix["drain_s"]:
                break
            while i < N and sched[i] <= now - t0:
                a = time.perf_counter()
                with span("bench/offer"):
                    res = svc.offer(self.payloads[self.pick[i]],
                                    client_ids=[int(self.cids[i])],
                                    uplink_id=(int(self.cids[i]),
                                               int(self.seqs[i])))
                self.offer_s[i] = time.perf_counter() - a
                if res.verdict not in ("rejected", "duplicate"):
                    accepted.append(i)
                i += 1
            with span("bench/tick"):
                svc.tick()
            if self.captured:
                jax.block_until_ready([b for _, b in self.captured])
                t = time.perf_counter() - t0
                for recs, blocks in self.captured:
                    for r, b in zip(recs, blocks):
                        k = accepted[decoded]
                        done[k] = t
                        self.order_mismatch += int(
                            int(r.client_ids[0]) != int(self.cids[k]))
                        if k in self.sample:
                            self.kept[k] = b
                        decoded += 1
                self.captured.clear()
            elif i < N and decoded >= len(accepted):
                wait = sched[i] - (time.perf_counter() - t0)
                if wait > 0:
                    time.sleep(wait)
        self.elapsed = time.perf_counter() - t0
        self.latency = done - sched
        self.refused = N - len(accepted)
        self.undecoded = len(accepted) - decoded
        return {"attempted": N, "failed": self.refused + self.undecoded}

    def end_to_end(self) -> dict:
        return {"offer_to_decoded_p95_ms":
                float(np.percentile(self.latency, 95)) * 1e3}

    def observed(self) -> dict:
        T = work.positions(self.model, self.image) * self.samples
        return {"offer_s": self.offer_s[np.isfinite(self.latency)],
                "decoded_records": self.service.decoded_records,
                "decode_dispatches": self.service.decode_dispatches,
                "record_positions": T, "elapsed_s": self.elapsed}

    def release(self):
        self._restore()
        self.ledger = ledger_imbalance(self.service.queue)
        self.service = None

    # ------------------------------------------------------------ check

    def answers(self):
        return {k: np.asarray(b) for k, b in self.kept.items()}

    def control_answers(self, numerics="control"):
        """The plain gather at the precision below HIGHEST: a one-hot
        matmul in three bf16 passes, which carries each table value as
        the sum of two bf16 parts (the low bits beyond them are lost)."""
        cb = jnp.asarray(self.params["codebook"], jnp.float32)
        hi = cb.astype(jnp.bfloat16).astype(jnp.float32)
        lo = (cb - hi).astype(jnp.bfloat16).astype(jnp.float32)
        return {k: np.asarray((hi + lo)[self._codes(k)]) for k in self.kept}

    def _codes(self, k):
        ref = self.cell.reference
        T = work.positions(self.model, self.image)
        codes = ref.unpack(self.words[self.pick[k]],
                           ref.code_bits(self.model), 1, self.samples * T)
        return codes.reshape(self.samples, T)

    def readings(self, got: dict) -> dict:
        """The widest gap between a sampled decode and the plain gather
        (infinite when a sampled record never came back)."""
        ref = self.cell.reference
        cb = np.asarray(self.params["codebook"])
        worst = 0.0
        for k, block in got.items():
            d = gather_diff(block, ref.decode_rows(self._codes(k), cb,
                                                   self.model))
            worst = max(worst, d["max_abs"])
        if len(got) < len(self.sample):
            worst = float("inf")
        return {"decode_max_abs_err": worst}

    def compare(self, r: dict) -> list:
        """The numbers that decide ``correct``, each beside its limit."""
        return [Compared(k, r[k], v) for k, v in self.limits.items()] + [
            Compared("decode_order_off", float(self.order_mismatch), 0),
            Compared("ledger_off_bytes", float(self.ledger), 0),
            Compared("undecoded", float(self.undecoded), 0),
        ]
