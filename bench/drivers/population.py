"""Closed-loop population rounds (OCTOPUS Steps 2-5 at population scale).

Each round draws ``participants`` clients uniformly from the
configuration's population, streams them through
``CohortEngine.round`` in cohorts of ``cohort`` (deploy fresh, one local
fine-tune step, as the reference takes, encode, pack, EMA statistics
folded into the Step-5 accumulator), then finishes the merge with ``OctopusServer.merge_stats``,
which registers a new codebook version. The next round deploys from it.

Images come from a pool made on the device in set-up, already cut into
cohort-sized blocks (on a mesh, already sharded over it): each cohort
takes the next block in turn, so nothing is generated or gathered inside
the window.

The window runs whole rounds until ``seconds`` have passed, so every
client it counts finished its round inside it. ``correct`` compares the
window's first round, all of its clients, with the plain reference.
"""
from __future__ import annotations

import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import work
from bench.harness.checks import Compared
from bench.harness.images import make_images, seed_key
from bench.harness.program import program_config, server_state

FIXED = float(1 << 24)        # the merge accumulator's fixed point


class Driver:
    def __init__(self, cell, seed: int, limits: dict):
        self.cell, self.seed, self.limits = cell, int(seed), limits
        c, m = cell.config, cell.mix
        self.model, self.client = c["model"], c["client"]
        self.image = c["input"]["image"]
        self.samples = c["samples_per_client"]
        self.population = c["population"]["clients"]
        self.participants = m["participants"]
        self.cohort = m["cohort"]
        self.check_block = m["check_block"]
        self.rng = np.random.default_rng(self.seed)
        self.rounds = []
        self.used = []

    # ----------------------------------------------------------- set-up

    def setup(self):
        from repro.sim import CohortEngine
        from repro.wire import OctopusServer
        ref, model, m = self.cell.reference, self.model, self.cell.mix
        t0 = time.perf_counter()
        self.cfg = program_config(model)
        self.params = jax.jit(partial(ref.init_params, model=model))(
            seed_key(self.seed, 1))
        self.server = OctopusServer(server_state(self.params), self.cfg)
        mesh = None
        if self.cell.chips > 1:
            from repro.launch.mesh import make_host_mesh
            mesh = make_host_mesh()
        self.engine = CohortEngine(self.cfg, lr=self.client["lr"],
                                   gamma=self.client["gamma"],
                                   n_local_steps=1, mesh=mesh)
        n_blocks = m["pool_clients"] // self.cohort
        x = make_images(seed_key(self.seed, 2),
                        n=n_blocks * self.cohort * self.samples,
                        size=self.image, channels=model["in_channels"],
                        identities=m["identities"])
        x = x.reshape((n_blocks, self.cohort, self.samples) + x.shape[1:])
        self.blocks = [x[i] for i in range(n_blocks)]
        self.feed = self.blocks
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            shard = NamedSharding(mesh, P("data"))
            self.feed = [jax.device_put(b, shard) for b in self.blocks]
        jax.block_until_ready(self.feed)
        t1 = time.perf_counter()
        # every shape the window uses, on a server the window never sees
        warm = OctopusServer(server_state(self.params), self.cfg)
        self._round(warm, np.arange(self.cohort))
        jax.block_until_ready(warm.state.params["codebook"])
        self.used.clear()
        print(f"set-up: weights and images {t1 - t0:.3f} s, warm-up "
              f"round {time.perf_counter() - t1:.3f} s", file=sys.stderr)

    def _data(self, ids):
        """The next pool block, whoever the cohort's clients are."""
        i = len(self.used) % len(self.feed)
        self.used.append(i)
        return self.feed[i]

    def _round(self, server, ids):
        from repro.sim import CohortPlan
        plan = CohortPlan.build(ids, self.cohort)
        out = self.engine.round(server.state, plan, self._data,
                                version=server.version)
        version = server.merge_stats(out.stats)
        jax.block_until_ready(out.payloads[-1].payload)
        return out, version

    # ----------------------------------------------------------- window

    def window(self, seconds: float, span):
        t0 = time.perf_counter()
        clients = nbytes = 0
        while True:
            ids = self.rng.choice(self.population, self.participants,
                                  replace=False)
            first = len(self.used)
            with span("bench/round"):
                out, version = self._round(self.server, ids)
            if not self.rounds:
                self.first = (self.used[first:], out.payloads, out.stats,
                              self.server.state.params["codebook"])
            self.rounds.append(version)
            clients += out.n_clients
            nbytes += out.nbytes
            if time.perf_counter() - t0 >= seconds:
                break
        self.elapsed = time.perf_counter() - t0
        self.clients, self.nbytes = clients, nbytes
        return {"attempted": clients, "failed": 0}

    def end_to_end(self) -> dict:
        return {"clients_per_s": self.clients / self.elapsed,
                "uplink_bytes_per_sample":
                    self.nbytes / (self.clients * self.samples)}

    def observed(self) -> dict:
        """What the per-layer readers may read."""
        T = work.positions(self.model, self.image)
        return {"clients": self.clients, "elapsed_s": self.elapsed,
                "encoded_records": self.clients,
                "record_positions": T * self.samples,
                "client_ops": work.client_ops(self.model, self.image,
                                              self.samples)}

    def release(self):
        """Free the program's state before the reference runs."""
        self.engine = self.server = self.feed = None

    # ------------------------------------------------------------ check

    def _sent_codes(self, payloads) -> np.ndarray:
        """The codes the first round sent, (clients, B*T[, S]), read back
        from the words with the reference's own unpacker."""
        ref, model = self.cell.reference, self.model
        T = work.positions(model, self.image) * self.samples
        S = work.codes_per_position(model)
        out = [ref.unpack(np.asarray(p.payload), ref.code_bits(model),
                          p.n_records, T * S) for p in payloads]
        codes = np.concatenate(out)
        return codes.reshape((len(codes), T) + ((S,) if S > 1 else ()))

    def answers(self) -> dict:
        """The program's answers for the window's first round: the codes
        each client sent, the merge accumulator (in count units) and the
        merged codebook."""
        blocks, payloads, stats, merged = self.first
        return {"blocks": blocks, "codes": self._sent_codes(payloads),
                "num": stats.num / FIXED, "den": stats.den / FIXED,
                "merged": np.asarray(merged)}

    def _clients(self, blocks):
        """(cohort block, first client, clients) in reference-sized steps."""
        for c, b in enumerate(blocks):
            for a in range(0, self.cohort, self.check_block):
                yield c * self.cohort + a, self.blocks[b][
                    a:a + self.check_block]

    def control_answers(self, numerics="control"):
        """The reference in the program's place, at other numerics (by
        default the control: one step below the stated precision)."""
        ref = self.cell.reference
        fn = ref.batched_round(self.model, self.client, numerics)
        blocks = self.first[0]
        codes, num, den = [], 0.0, 0.0
        for a, x in self._clients(blocks):
            zero = jnp.zeros((len(x),) + self._code_shape(), jnp.int32)
            out = fn(self.params, x, zero)
            n, d = ref.merge(out["counts"], out["codebook"])
            num, den = num + n, den + d
            codes.append(np.asarray(out["codes"]))
        return {"blocks": blocks,
                "codes": np.concatenate(codes).reshape(
                    (-1,) + self._code_shape()),
                "num": num, "den": den,
                "merged": (num / den[:, None]).astype(np.float32)}

    def _code_shape(self):
        T = work.positions(self.model, self.image) * self.samples
        S = work.codes_per_position(self.model)
        return (T, S) if S > 1 else (T,)

    def readings(self, ans: dict) -> dict:
        """Every number the comparison can read, for one set of answers."""
        ref, gamma = self.cell.reference, self.client["gamma"]
        fn = ref.batched_round(self.model, self.client, "reference")
        cb0 = np.asarray(self.params["codebook"], np.float64)
        acc = {"gap_max": 0.0, "gap_sum": 0.0, "mismatches": 0,
               "num": 0.0, "den": 0.0, "sums": 0.0, "ratio": 0.0,
               "counts": 0.0}
        for a, x in self._clients(ans["blocks"]):
            sent = ans["codes"][a:a + len(x)]
            out = jax.device_get(fn(self.params, x, jnp.asarray(sent)))
            acc["gap_max"] = max(acc["gap_max"], float(out["gap_max"].max()))
            acc["gap_sum"] += float(out["gap_sum"].sum())
            acc["mismatches"] += int(out["mismatches"].sum())
            n, d = ref.merge(out["counts"], out["codebook"])
            acc["num"] += n
            acc["den"] += d
            counts, ratio = ref.ema_weights(out["sent_n"], gamma)
            acc["counts"] += counts.sum(axis=0)
            acc["ratio"] += ratio.sum(axis=0)
            acc["sums"] += np.einsum("ck,ckm->km", ratio,
                                     np.asarray(out["sent_s"], np.float64))
        n_codes = ans["codes"].size
        want = acc["num"] / acc["den"][:, None]
        got = np.asarray(ans["merged"], np.float64)
        atom = np.linalg.norm(got - want, axis=1) / np.linalg.norm(want,
                                                                   axis=1)
        # the latent sums the sent codes assign, out of the accumulator
        # (num = sum over clients of ratio * (gamma cb0 + (1-gamma) sums))
        sums = (ans["num"] - gamma * cb0 * acc["ratio"][:, None]) / (
            1.0 - gamma)
        return {
            "code_mismatch_pct": 100.0 * acc["mismatches"] / n_codes,
            "code_gap_max": acc["gap_max"],
            "code_gap_mean": acc["gap_sum"] / n_codes,
            "merge_atom_err": float(atom.max()),
            "merge_err": float(np.linalg.norm(got - want)
                               / np.linalg.norm(want - cb0)),
            # the merged dictionary's change from the deployed one: the
            # gap between its norm and the reference's, over the latter
            "merge_norm_gap": float(abs(np.linalg.norm(got - cb0)
                                        - np.linalg.norm(want - cb0))
                                    / np.linalg.norm(want - cb0)),
            "latent_sum_err": float(np.linalg.norm(sums - acc["sums"])
                                    / np.linalg.norm(acc["sums"])),
            "votes_off": float(np.abs(np.rint(
                (ans["den"] - acc["counts"]) / (1.0 - gamma))).sum()),
        }

    def compare(self, r: dict) -> list:
        """The numbers that decide ``correct``, each beside its limit:
        those the limits file names, and the exact ones."""
        per_client = work.packed_bytes(
            self.model, work.positions(self.model, self.image)
            * self.samples * work.codes_per_position(self.model))
        out = [Compared(k, r[k], v) for k, v in self.limits.items()]
        return out + [
            Compared("votes_off", r["votes_off"], 0),
            Compared("uplink_bytes_off",
                     abs(self.nbytes - per_client * self.clients), 0),
            Compared("versions_off", float(
                self.rounds != list(range(1, len(self.rounds) + 1))), 0),
        ]
