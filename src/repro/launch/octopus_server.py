"""Code-server launch entry: scheduler scenarios over the cohort engine.

Drives the repro.server subsystem end-to-end — pretrain a global DVQ-AE,
replay one (or every) STANDARD_SCENARIOS traffic profile through
``CohortEngine.run_traffic`` into an ``OctopusServer`` (fresh deploys
each round, cohorts per delivery fate on one shared ``UplinkQueue``,
Step 5 merges every ``merge_every`` rounds), then train the multi-task
heads from one decode of the versioned CodeStore. Prints per-scenario
rounds/sec, measured uplink bytes, store/version state and task
accuracies.

    PYTHONPATH=src python -m repro.launch.octopus_server \
        [--scenario full|partial|churn|all] [--slots 8] [--rounds 8] \
        [--smoke]

``--smoke`` shrinks every knob to CI scale (a few seconds on CPU).
"""
from __future__ import annotations

import argparse
import itertools
import time
from typing import Dict, NamedTuple

import jax
import numpy as np

from repro.core import octopus as OC
from repro.core.dvqae import DVQAEConfig
from repro.data import make_images, partition_stacked, stacked_batches
from repro.server import (STANDARD_SCENARIOS, MultiTaskTrainer,
                          RoundScheduler, TaskSpec, UplinkQueue)
from repro.sim import CohortEngine
from repro.wire import OctopusServer


class ScenarioRun(NamedTuple):
    """What one traffic scenario left behind."""
    wire: OctopusServer        # registry + store; ``features()`` decodes
    queue: UplinkQueue         # the measured §2.8 byte ledger
    participants: int          # clients drawn per round
    n_merges: int
    acc: Dict[str, float]      # task -> head accuracy on the store
    rounds_per_sec: float      # rounds 1.. (round 0 pays compilation)


def run_scenario(name, scenario, *, engine, server, stacked, slots, rounds,
                 local_batch, probe_steps, key, index: int = 0,
                 verbose: bool = True) -> ScenarioRun:
    """Drive one traffic scenario through ``engine.run_traffic`` (a
    ``CohortEngine``; one cohort per delivery group), then train the two
    standard heads from one store decode. Shared by this CLI and
    ``benchmarks.run::bench_server``.
    """
    if rounds < 2:
        raise ValueError("need rounds >= 2: round 0 is the compile warmup, "
                         "rounds/sec is timed over the rest")
    sched = RoundScheduler(slots, scenario.sched,
                           key=jax.random.fold_in(key, index))
    wire, queue = OctopusServer(server, engine.cfg), UplinkQueue()
    batches = list(itertools.islice(
        stacked_batches(stacked, local_batch, epochs=rounds), rounds))
    started = {}

    def data_fn(ids):
        r = sched.round - 1                      # the round being drawn
        started.setdefault(r, time.time())
        return batches[r].x[np.asarray(ids)]

    def labels_fn(ids):
        b, ids = batches[sched.round - 1], np.asarray(ids)
        return {"content": b.content[ids], "style": b.style[ids]}

    history = engine.run_traffic(
        wire, sched, data_fn, labels_fn=labels_fn, cohort_size=slots,
        n_rounds=rounds, merge_every=scenario.merge_every, queue=queue)
    rps = (rounds - 1) / max(time.time() - started[1], 1e-9)

    feats, labels = wire.features()
    tasks = [TaskSpec("content", int(stacked.content.max()) + 1),
             TaskSpec("style", int(stacked.style.max()) + 1)]
    trainer = MultiTaskTrainer(key, tasks, int(feats[0].size))
    trainer.fit(key, feats, labels, steps=probe_steps, batch=64)
    acc = trainer.accuracy(feats, labels)
    run = ScenarioRun(wire=wire, queue=queue, participants=sched.k,
                      n_merges=sum(h.merged_version is not None
                                   for h in history),
                      acc=acc, rounds_per_sec=rps)
    if verbose:
        print(f"[{name}] {rps:.2f} rounds/sec | bytes sent="
              f"{queue.bytes_sent} delivered={queue.bytes_delivered} "
              f"dropped={queue.bytes_dropped} | "
              f"store {len(wire.store)} recs v{list(wire.store.versions)} "
              f"({run.n_merges} merges) | "
              + " ".join(f"{t}={a:.3f}" for t, a in acc.items()))
    return run


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", default="all",
                    choices=sorted(STANDARD_SCENARIOS) + ["all"])
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--local-batch", type=int, default=8)
    ap.add_argument("--codebook", type=int, default=64)
    ap.add_argument("--probe-steps", type=int, default=150)
    ap.add_argument("--pretrain-steps", type=int, default=80)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.smoke:
        args.slots, args.rounds, args.local_batch = 4, 4, 4
        args.probe_steps, args.pretrain_steps = 20, 20

    key = jax.random.PRNGKey(args.seed)
    cfg = DVQAEConfig(kind="image", in_channels=3, hidden=16, latent_dim=16,
                      codebook_size=args.codebook, n_res_blocks=1)
    data = make_images(key, max(args.slots * args.local_batch * args.rounds,
                                args.slots * 16), size=16, n_identities=4)
    server, out = OC.server_pretrain(key, OC.server_init(key, cfg), cfg,
                                     data.x, steps=args.pretrain_steps)
    if out is not None:
        print(f"pretrain recon loss: {float(out.recon_loss):.4f}")

    stacked = partition_stacked(data, args.slots, regime="skewed", skew=0.2)
    engine = CohortEngine(cfg, lr=1e-4, gamma=0.95)
    names = sorted(STANDARD_SCENARIOS) if args.scenario == "all" \
        else [args.scenario]
    for i, name in enumerate(names):
        run_scenario(name, STANDARD_SCENARIOS[name], engine=engine,
                     server=server, stacked=stacked, slots=args.slots,
                     rounds=args.rounds, local_batch=args.local_batch,
                     probe_steps=args.probe_steps, key=key, index=i)


if __name__ == "__main__":
    main()
