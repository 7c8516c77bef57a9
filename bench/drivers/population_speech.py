"""Closed-loop population rounds of the speech DVQ-AE.

The rounds, the window, the merge and the comparison with the reference
are ``population.py``'s. What differs is what reads the input's shape:
the pool is seeded keyword clips of raw waveform (``clips.py``), one
speaker per client, made on the device in set-up and already cut into
cohort blocks; a client record holds clips x T/4 latent positions; and a
client's operations are counted by ``work1d.py``. One chip.
"""
from __future__ import annotations

import sys
import time
from functools import partial
from pathlib import Path

import jax
import numpy as np

from bench.harness import work, work1d
from bench.harness.checks import Compared
from bench.harness.clips import make_clips
from bench.harness.images import seed_key
from bench.harness.loader import load_module
from bench.harness.program import program_config, server_state

_population = load_module(Path(__file__).with_name("population.py"),
                          "driver", "population")


class Driver(_population.Driver):
    def __init__(self, cell, seed: int, limits: dict):
        self.cell, self.seed, self.limits = cell, int(seed), limits
        c, m = cell.config, cell.mix
        self.model, self.client = c["model"], c["client"]
        self.clip, self.rate = c["input"]["samples"], c["input"]["rate_hz"]
        self.samples = c["samples_per_client"]
        self.population = c["population"]["clients"]
        self.participants = m["participants"]
        self.cohort = m["cohort"]
        self.check_block = m["check_block"]
        self.rng = np.random.default_rng(self.seed)
        self.rounds = []
        self.used = []

    @property
    def record_positions(self) -> int:
        """Latent positions of one client record: clips x T/4."""
        return work1d.positions(self.model, self.clip) * self.samples

    def setup(self):
        from repro.sim import CohortEngine
        from repro.wire import OctopusServer
        ref, model, m = self.cell.reference, self.model, self.cell.mix
        t0 = time.perf_counter()
        self.cfg = program_config(model)
        self.params = jax.jit(partial(ref.init_params, model=model))(
            seed_key(self.seed, 1))
        self.server = OctopusServer(server_state(self.params), self.cfg)
        self.engine = CohortEngine(self.cfg, lr=self.client["lr"],
                                   gamma=self.client["gamma"],
                                   n_local_steps=1)
        self.blocks = list(make_clips(
            seed_key(self.seed, 2), blocks=m["pool_clients"] // self.cohort,
            cohort=self.cohort, clips=self.samples, samples=self.clip,
            rate=self.rate, speakers=m["speakers"], words=m["words"]))
        self.feed = self.blocks
        jax.block_until_ready(self.feed)
        t1 = time.perf_counter()
        # every shape the window uses, on a server the window never sees
        warm = OctopusServer(server_state(self.params), self.cfg)
        self._round(warm, np.arange(self.cohort))
        jax.block_until_ready(warm.state.params["codebook"])
        self.used.clear()
        print(f"set-up: weights and clips {t1 - t0:.3f} s, warm-up "
              f"round {time.perf_counter() - t1:.3f} s", file=sys.stderr)

    def observed(self) -> dict:
        """What the per-layer readers may read."""
        return {"clients": self.clients, "elapsed_s": self.elapsed,
                "encoded_records": self.clients,
                "record_positions": self.record_positions,
                "client_ops": work1d.client_ops(self.model, self.clip,
                                                self.samples)}

    def _code_shape(self):
        S = work.codes_per_position(self.model)
        T = self.record_positions
        return (T, S) if S > 1 else (T,)

    def _sent_codes(self, payloads) -> np.ndarray:
        """The codes the first round sent, (clients, T[, S]), read back
        from the words with the reference's own unpacker."""
        ref, shape = self.cell.reference, self._code_shape()
        codes = np.concatenate([
            ref.unpack(np.asarray(p.payload), ref.code_bits(self.model),
                       p.n_records, int(np.prod(shape))) for p in payloads])
        return codes.reshape((len(codes),) + shape)

    def compare(self, r: dict) -> list:
        """The numbers that decide ``correct``, each beside its limit:
        those the limits file names, and the exact ones."""
        per_client = work.packed_bytes(self.model,
                                       int(np.prod(self._code_shape())))
        out = [Compared(k, r[k], v) for k, v in self.limits.items()]
        return out + [
            Compared("votes_off", r["votes_off"], 0),
            Compared("uplink_bytes_off",
                     abs(self.nbytes - per_client * self.clients), 0),
            Compared("versions_off", float(
                self.rounds != list(range(1, len(self.rounds) + 1))), 0),
        ]
