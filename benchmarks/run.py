"""Benchmark harness — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig4,fig5,...]
    PYTHONPATH=src python -m benchmarks.run --section server --smoke

Emits ``section,name,value[,extra]`` CSV lines plus wall-time per section,
and writes each section's rows as a machine-readable ``BENCH_<section>.json``
artifact (``{"section", "rows": [{name, value, extra}], "wall_s"}``) in the
working directory so benchmark trajectories can be tracked across commits.
Paper targets:
  fig4     downstream accuracy: centralized vs FL variants vs OCTOPUS
  fig5     privatization: private-attribute accuracy + conditional entropy
  table1   disentanglement on/off across codebook sizes
  fig9     multi-task probes on latent codes vs raw baseline
  sec2_8   communication-overhead accounting (measured bytes)
  sec3_8   time overheads (encode latency, probe vs conv train time)
  kernels  Pallas kernel microbenchmarks vs jnp reference
  gsvq     GSVQ (groups x slices) accuracy vs bits-per-position
  sim      batched multi-client engine (repro.sim) throughput + uplink
  server   code-server runtime (repro.server): rounds/sec, decode
           amortization, bytes-per-accuracy across traffic scenarios
  decode   fused packed-code->feature decode (kernels/decode_codes.py)
           vs the unpack-then-dequantize baseline
  encode   fused client uplink (kernels/encode_codes.py): single-encode
           + one quantize-pack-stats dispatch vs the seed pipeline that
           re-ran the network and materialized distances + indices
  wire     unified wire protocol (repro/wire): OctopusClient facade
           round vs the PR-4 fused round — bit-identical words,
           dispatch-count-neutral, plus the CodePayload->store roundtrip
  privacy  red-team sweep (repro/privacy): inference-attack advantage
           vs disentanglement strength / K / GSVQ grouping, the
           leaky-control teeth check, and oblivious-store overhead

``privacy`` CSV schema (rows ``privacy,<name>,<value>[,extra]``):
  harness_matches_wire      partial-IN harness encoder == facade wire
                            at both endpoints (packed words, bit-exact)
  leaky_control_advantage   attribute-attack advantage on the REAL
                            facade wire with IN off — MUST clear chance
                            (the harness-has-teeth gate)
  privatized_advantage      same attack, IN on — must sit ≈ chance
  attr_advantage/disent_s<s>   advantage at disentanglement strength s
  attr_advantage/K<K>_{leaky|priv}        advantage vs codebook size
  attr_advantage/gsvq_g<G>s<S>_{leaky|priv}  advantage vs GSVQ grouping
  membership_{leaky|privatized}_advantage  client re-identification
                            (round-2 members vs never-seen holdouts)
  oblivious_parity_bitexact oblivious store == plain sharded store
                            (codes + every (client, round) get)
  oblivious_touch_ratio     partitions touched per useful partition
                            (the access-pattern-hiding cost)
  oblivious_get_overhead    wall ratio oblivious/plain on one identical
                            query workload (OMLO methodology)

``wire`` CSV schema (rows ``wire,<name>,<value>[,extra]``):
  bit_identical_to_fused    facade payload words == pure round_words core
  facade_samples_per_sec    jitted facade round core (wire.round_words)
  facade_encoder_passes     COUNTED encoder invocations of one facade
                            round (extra: the pure core's count)
  facade_encode_dispatches  COUNTED ops.encode_codes dispatches (extra:
                            the pure core's count)
  payload_bytes             measured CodePayload.nbytes of one round
  store_bytes_match         store.total_bytes == payload.nbytes after
                            OctopusServer.ingest
  decoded_samples           rows decoded by OctopusServer.features()

``encode`` CSV schema (rows ``encode,<cfg>_<name>,<value>[,extra]``):
  fused_samples_per_sec     one uplink round (Steps 3-5 tail) as ONE
                            dispatch: single encoder pass feeding
                            ops.encode_codes (quantize + pack + EMA
                            stats fused)
  baseline_samples_per_sec  the same round through the seed entry
                            points: client_transmit (forward -> indices
                            -> pack) then client_codebook_refresh
                            (network pass again -> ema_update), each its
                            own dispatch with its own network pass
  fused_gbps / baseline_gbps   measured packed-uplink GB/s of each path
  speedup                   baseline time / fused time (same jit regime)
  encoder_passes_per_round  COUNTED encoder invocations of one
                            client_round (extra: the seed path's count)

``decode`` CSV schema (rows ``decode,<cfg>_<name>,<value>[,extra]``):
  fused_samples_per_sec     decoded samples/s straight from the packed
                            word stream (ops.decode_codes)
  baseline_samples_per_sec  same decode as unpack_codes -> dequantize
                            (two materialized hops)
  fused_gbps / baseline_gbps   measured packed-payload GB/s of each path
  speedup                   baseline time / fused time (same jit regime)

``server`` CSV schema (rows ``server,<scenario>_<name>,<value>[,extra]``):
  rounds_per_sec       scheduler-driven rounds/sec through the runtime
                       (post-compile)
  participants         scheduled participants per round
  bytes_delivered      MEASURED packed bytes landed in the CodeStore
  bytes_sent           measured bytes incl. dropped / in-flight
  store_records        records buffered (extra: codebook versions held)
  acc_<task>           multi-task head accuracy from ONE store decode
  bytes_per_point      delivered bytes per content-accuracy point
  decode_amortization  measured end-to-end: per-task pipeline time
                       (re-decode store + fit each head) / shared
                       pipeline time (one decode, one multi-head fit)
  decode_shared_pipeline_ms   wall ms of the shared pipeline leg
continuous-ingest soak rows (``server,continuous_*`` / ``admission_*``):
  continuous_uplinks_per_sec  HEADLINE: sustained uplinks/sec through
                       the clocked ContinuousIngestService under churn,
                       with backpressure and a rolling codebook
                       migration engaged inside the timed window
  continuous_ticks / continuous_participants   soak extent
  admission_<verdict> / admission_<verdict>_bytes   admission-control
                       histogram (accepted/migrated/deferred/rejected);
                       refused bytes stay on the §2.8 ledger
  continuous_bytes_delivered / continuous_bytes_refused   ledger split
  continuous_store_partitions   (version, shard) ring buffers in use
  continuous_migrations         rolling v_n -> v_{n+1} windows completed
  continuous_decode_amortization   records decoded per fused dispatch
                       by the background bulk-decode batches
chaos-plane rows (``server,goodput_under_faults`` etc.):
  goodput_under_faults  delivered B/s of the SAME soak run through a
                       journaled FaultyChannel (drop / duplicate /
                       reorder / delay / corrupt / truncate + retries)
                       — the §2.8 ledger stays conserved under chaos
  faults_injected / fault_retries   chaos extent (extra: per-kind)
  recovery_time_s      crash drill: snapshot + journal replay back to
                       the exact pre-kill tick/verdicts/ledger

``sim`` CSV schema (all rows ``sim,<name>,<value>[,<extra>]``):
  n_clients            population size advanced per jitted call
  round_ms             mean wall ms per engine round (Steps 2-5, jitted)
  clients_per_sec      n_clients * rounds / wall — the headline
                       scale metric (a Python client loop is the 1x
                       baseline)
  speedup_vs_loop      measured speedup over that Python client loop
  bytes_per_round      MEASURED size of the round's bit-packed uplink
                       payload
  bits_per_code        bits per packed code index
  bytes_per_round_int32  same indices as unpacked int32 (the naive
                       transmission the codec replaces)
  pack_ratio           bytes_per_round_int32 / bytes_per_round
  ingest_rounds        rounds accumulated in the server CodeStore
  ingest_total_bytes   measured bytes across the buffered rounds
  ingest_probe_acc     Step-6 probe accuracy trained from the store
  cohort_parity_bitexact   cohort-streamed round == single full-population
                       round (merge stats + payload words + bytes, ALL
                       array_equal)
  cohort_parity_pop    population size the parity gate checked
  cohort_size          clients per streamed cohort (the compiled unit)
  pop<N>_clients_per_sec   clients/sec of a cohort-streamed population
                       round at N simulated clients
  pop<N>_round_s       wall seconds of that streamed round
  pop<N>_bytes         Σ measured per-cohort uplink bytes of that round
  pop<N>_cohorts       cohorts dispatched in that round
  pop_max_clients      largest population in the scaling curve — the
                       ROADMAP 100k+ target rides here

Scaling-curve methodology: clients deploy fresh from the server each
round (cross-device regime), every cohort reuses ONE compiled engine
round (jit cache keyed on the cohort shape), per-cohort Step-5 stats
fold into the exactly-associative int64 fixed-point accumulator, and
clients/sec = N / wall(streamed round) AFTER a warm-up cohort compiles
the shape. Peak memory is one cohort's state — the population's stacked
state never exists, which is what lets N reach 100k+ on one host.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from benchmarks import common as C

_ROWS = []      # every _emit row, grouped into BENCH_<section>.json by main()


def _coerce(value):
    """BENCH artifacts carry real JSON values: numeric strings become
    numbers and True/False become booleans, so cross-PR trend tooling can
    diff rows without parsing. ``extra`` stays the only string field."""
    if isinstance(value, str):
        s = value.strip()
        if s in ("True", "False"):
            return s == "True"
        try:
            return int(s)
        except ValueError:
            pass
        try:
            return float(s)
        except ValueError:
            return value
    return value


def _emit(section, name, value, extra=""):
    _ROWS.append({"section": section, "name": name, "value": _coerce(value),
                  "extra": str(extra)})
    print(f"{section},{name},{value}{',' + str(extra) if extra else ''}",
          flush=True)


def _write_artifact(section, wall_s):
    """Dump one section's rows as machine-readable BENCH_<section>.json."""
    rows = [{k: r[k] for k in ("name", "value", "extra")}
            for r in _ROWS if r["section"] == section]
    with open(f"BENCH_{section}.json", "w") as f:
        json.dump({"section": section, "wall_s": round(wall_s, 1),
                   "rows": rows}, f, indent=1)


# ------------------------------------------------------------------- fig 4

def bench_fig4(key):
    """Downstream (content) accuracy across schemes (Fig. 4)."""
    from repro.core.downstream import conv_classifier, init_conv_classifier
    from repro.core.fedavg import FedConfig, fedavg_train
    from repro.core import downstream as DS

    pipe = C.build_pipeline(key, codebook_size=256)
    n_classes = 8
    y_tr, y_te = pipe.train.content, pipe.test.content

    # centralized on raw data (upper baseline)
    acc = C.train_conv_on_raw(key, pipe.train.x, y_tr, pipe.test.x, y_te)
    _emit("fig4", "centralized", f"{acc:.4f}")

    # centralized + DP (clip + noise during training)
    clf0 = init_conv_classifier(key, in_channels=3, n_classes=n_classes)
    dp = fedavg_train(key, conv_classifier, clf0, [pipe.train],
                      C.content_label,
                      FedConfig(rounds=C.FED_ROUNDS, dp_clip=1.0,
                                dp_noise=0.05, local_epochs=8))
    _emit("fig4", "centralized_dp",
          f"{DS.accuracy(conv_classifier, dp, pipe.test.x, y_te):.4f}")

    # federated variants
    def fed(shards, fc, shared=None, tag=""):
        p0 = init_conv_classifier(key, in_channels=3, n_classes=n_classes)
        p = fedavg_train(key, conv_classifier, p0, shards, C.content_label,
                         fc, shared_data=shared)
        a = DS.accuracy(conv_classifier, p, pipe.test.x, y_te)
        _emit("fig4", tag, f"{a:.4f}")
        return a

    base_fc = FedConfig(rounds=C.FED_ROUNDS, local_epochs=8)
    fed(pipe.shards_iid, base_fc, tag="fed_iid")
    fed(pipe.shards_worst, base_fc, tag="fed_noniid_worst")
    fed(pipe.shards_skew, base_fc, tag="fed_noniid_moderate")
    fed(pipe.shards_worst, FedConfig(rounds=C.FED_ROUNDS, prox_mu=0.1,
                                     local_epochs=8), tag="fedprox_worst")
    fed(pipe.shards_worst, base_fc, shared=pipe.atd, tag="fed_datashare")
    fed(pipe.shards_iid, FedConfig(rounds=C.FED_ROUNDS, dp_clip=1.0,
                                   dp_noise=0.05, local_epochs=8),
        tag="fed_iid_dp")

    # OCTOPUS across codebook sizes
    for B in (32, 64, 128, 256):
        p = pipe if B == 256 else C.build_pipeline(key, codebook_size=B)
        acc = C.train_probe_on_codes(key, p, p.train.content, p.test.content)
        _emit("fig4", f"octopus_B{B}", f"{acc:.4f}")


# ------------------------------------------------------------------- fig 5

def bench_fig5(key):
    """Privatization: identity (style) recognition accuracy on raw vs
    OCTOPUS public codes; conditional entropy per Thm. 1 (Fig. 5 + Fig. 7)."""
    from repro import privacy as PV
    pipe = C.build_pipeline(key, codebook_size=256)

    # adversary on RAW data (centralized leak baseline)
    acc_raw = C.train_conv_on_raw(key, pipe.train.x, pipe.train.style,
                                  pipe.test.x, pipe.test.style)
    _emit("fig5", "identity_acc_raw_centralized", f"{acc_raw:.4f}")

    # adversary on released public codes Z•
    adv = PV.train_adversary(key, pipe.train_codes, pipe.train.style,
                             C.N_IDENTITIES, steps=C.PROBE_STEPS)
    m_pub = PV.evaluate_adversary(adv, pipe.test_codes, pipe.test.style,
                                  C.N_IDENTITIES)
    _emit("fig5", "identity_acc_octopus_public", f"{m_pub.accuracy:.4f}")
    _emit("fig5", "cond_entropy_bits_public",
          f"{m_pub.conditional_entropy_bits:.4f}")

    # adversary on the private component Z∘ (should leak MORE)
    from repro.core.dvqae import forward as fwd
    out_tr = fwd(pipe.server.params, pipe.cfg, pipe.train.x)
    out_te = fwd(pipe.server.params, pipe.cfg, pipe.test.x)
    priv_tr = jnp.broadcast_to(out_tr.latent.private,
                               out_tr.latent.public.shape)
    priv_te = jnp.broadcast_to(out_te.latent.private,
                               out_te.latent.public.shape)
    adv2 = PV.train_adversary(key, priv_tr, pipe.train.style,
                              C.N_IDENTITIES, steps=C.PROBE_STEPS)
    m_prv = PV.evaluate_adversary(adv2, priv_te, pipe.test.style,
                                  C.N_IDENTITIES)
    _emit("fig5", "identity_acc_octopus_private", f"{m_prv.accuracy:.4f}")
    _emit("fig5", "cond_entropy_bits_private",
          f"{m_prv.conditional_entropy_bits:.4f}")

    _emit("fig5", "claim_public_much_lower",
          str(m_pub.accuracy < 0.6 * acc_raw))
    _emit("fig5", "claim_private_leaks_more",
          str(m_prv.accuracy > m_pub.accuracy))

    # utility retained on the same released codes
    util = C.train_probe_on_codes(key, pipe, pipe.train.content,
                                  pipe.test.content)
    _emit("fig5", "content_acc_on_public_codes", f"{util:.4f}")


# ------------------------------------------------------------------ table 1

def bench_table1(key):
    """Identity accuracy with/without disentanglement across codebook
    sizes (Table 1 / Fig. 8)."""
    from repro import privacy as PV
    for B in (32, 64, 128):
        row = []
        for apply_in in (True, False):
            pipe = C.build_pipeline(key, codebook_size=B, apply_in=apply_in)
            adv = PV.train_adversary(key, pipe.train_codes, pipe.train.style,
                                     C.N_IDENTITIES, steps=C.PROBE_STEPS)
            m = PV.evaluate_adversary(adv, pipe.test_codes, pipe.test.style,
                                      C.N_IDENTITIES)
            row.append(m.accuracy)
        _emit("table1", f"B{B}_with_disent", f"{row[0]:.4f}")
        _emit("table1", f"B{B}_without_disent", f"{row[1]:.4f}")
        _emit("table1", f"B{B}_disent_helps", str(row[0] <= row[1] + 0.05))


# ------------------------------------------------------------------- fig 9

def bench_fig9(key):
    """Multi-task: several binary attributes from ONE set of latent codes
    vs per-task conv baselines (Fig. 9)."""
    pipe = C.build_pipeline(key, codebook_size=256)
    tasks = {
        "is_round": lambda c: (c <= 1).astype(jnp.int32),
        "has_bar": lambda c: ((c == 6) | (c == 7)).astype(jnp.int32),
        "is_diag": lambda c: ((c == 4) | (c == 5)).astype(jnp.int32),
        "high_class": lambda c: (c >= 4).astype(jnp.int32),
    }
    t0 = time.time()
    for name, fn in tasks.items():
        acc = C.train_probe_on_codes(key, pipe, fn(pipe.train.content),
                                     fn(pipe.test.content))
        _emit("fig9", f"octopus_probe_{name}", f"{acc:.4f}")
    probe_t = time.time() - t0
    t0 = time.time()
    for name, fn in tasks.items():
        acc = C.train_conv_on_raw(key, pipe.train.x, fn(pipe.train.content),
                                  pipe.test.x, fn(pipe.test.content))
        _emit("fig9", f"conv_raw_{name}", f"{acc:.4f}")
    conv_t = time.time() - t0
    _emit("fig9", "probe_total_s", f"{probe_t:.2f}")
    _emit("fig9", "conv_total_s", f"{conv_t:.2f}")


# ------------------------------------------------------------------ §2.8

def bench_sec2_8(key):
    """Communication overheads with bytes measured from THIS system."""
    from repro.core.overheads import (CommModel, comparison_table,
                                      multi_task_bytes)
    from repro.core.downstream import init_conv_classifier
    pipe = C.build_pipeline(key, codebook_size=256)
    clf = init_conv_classifier(key, in_channels=3, n_classes=8)
    model_bytes = sum(l.size * 4 for l in jax.tree.leaves(clf))
    n_samples = pipe.train.x.shape[0]
    code_bytes = pipe.bytes_transmitted // max(n_samples, 1)
    cb = pipe.server.params["codebook"]
    c = CommModel(
        n_clients=C.N_CLIENTS, model_bytes=model_bytes,
        n_samples=n_samples, n_epochs=100,
        code_bytes_per_sample=code_bytes,
        smashed_bytes_per_sample=int(pipe.train_codes[0].size) * 4,
        codebook_bytes=cb.size * 4, downstream_model_bytes=model_bytes)
    for k, v in comparison_table(c).items():
        _emit("sec2_8", k, f"{v:.3e}" if isinstance(v, float) else v)
    mt = multi_task_bytes(c, 10)
    _emit("sec2_8", "multitask10_federated", mt["federated"])
    _emit("sec2_8", "multitask10_octopus", mt["octopus"])
    _emit("sec2_8", "raw_bytes_per_sample", pipe.train.x[0].size * 4)
    _emit("sec2_8", "code_bytes_per_sample", code_bytes)


# ------------------------------------------------------------------ §3.8

def bench_sec3_8(key):
    """Time overheads: per-sample encode latency; probe vs conv train."""
    from repro.wire import OctopusClient
    pipe = C.build_pipeline(key, codebook_size=256)
    client = OctopusClient(pipe.server, pipe.cfg)
    x1 = pipe.test.x[:1]
    payload = client.transmit(x1)                   # compile
    t0 = time.time()
    for _ in range(20):
        payload = client.transmit(x1)
    # the facade transmit IS the fused Steps 3-4 tail: quantize + bit-pack
    # in one dispatch, the payload is what hits the uplink
    jax.block_until_ready(payload.payload)
    _emit("sec3_8", "encode_ms_per_sample", f"{(time.time()-t0)/20*1e3:.2f}")

    t0 = time.time()
    C.train_probe_on_codes(key, pipe, pipe.train.content, pipe.test.content)
    _emit("sec3_8", "probe_train_s", f"{time.time()-t0:.2f}")
    t0 = time.time()
    C.train_conv_on_raw(key, pipe.train.x, pipe.train.content, pipe.test.x,
                        pipe.test.content)
    _emit("sec3_8", "conv_train_s", f"{time.time()-t0:.2f}")


# ---------------------------------------------------------------- kernels

def bench_kernels(key):
    """Microbenchmarks: Pallas (interpret on CPU) vs jnp reference."""
    from repro.kernels import ops, ref

    z = jax.random.normal(key, (2048, 64))
    cb = jax.random.normal(jax.random.PRNGKey(1), (256, 64))

    def timeit(fn, *args, n=5):
        out = fn(*args)
        jax.block_until_ready(out)
        t0 = time.time()
        for _ in range(n):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.time() - t0) / n * 1e6

    jref = jax.jit(ref.vq_nearest_ref)
    _emit("kernels", "vq_nn_ref_us", f"{timeit(jref, z, cb):.0f}")
    _emit("kernels", "vq_nn_pallas_interpret_us",
          f"{timeit(lambda a, b: ops.vq_nearest(a, b), z, cb):.0f}")

    q = jax.random.normal(key, (1, 512, 4, 64))
    jref2 = jax.jit(lambda q: ref.flash_attention_ref(q, q, q))
    _emit("kernels", "flash_ref_us", f"{timeit(jref2, q):.0f}")

    x = jax.random.normal(key, (4096, 1024))
    s = jnp.ones((1024,))
    jref3 = jax.jit(ref.rmsnorm_ref)
    _emit("kernels", "rmsnorm_ref_us", f"{timeit(jref3, x, s):.0f}")
    _emit("kernels", "interpret_mode", 1,
          extra="pallas timed in interpret mode on CPU; "
                "TPU timings require hardware")


def bench_gsvq(key):
    """§3.1 group setups: GSVQ (groups x slices) vs plain VQ — accuracy and
    bits-per-position trade-off."""
    from repro.core.gsvq import gsvq_bits_per_position
    for (g, sl) in ((1, 1), (4, 1), (8, 2), (16, 4)):
        pipe = C.build_pipeline(key, codebook_size=64, n_groups=g,
                                n_slices=sl)
        acc = C.train_probe_on_codes(key, pipe, pipe.train.content,
                                     pipe.test.content)
        bits = (gsvq_bits_per_position(g, sl) if g > 1
                else 6)                      # log2(64) plain VQ
        _emit("gsvq", f"G{g}_S{sl}_acc", f"{acc:.4f}")
        _emit("gsvq", f"G{g}_S{sl}_bits_per_pos", bits)


# ------------------------------------------------------------------- sim

def bench_sim(key):
    """Batched multi-client engine: clients/sec of one jitted population
    round (Steps 2-5) vs a Python client loop, plus the round's measured
    bit-packed uplink (schema in the module docstring)."""
    from repro.core import octopus as OC
    from repro.core.dvqae import DVQAEConfig
    from repro.data import make_images, partition_stacked, stacked_batches
    from repro.kernels.ops import pack_codes
    from repro.server import CodeStore
    from repro.sim import SimEngine

    n_clients = 16 if C.QUICK else 64
    local_batch = 8
    cfg = DVQAEConfig(kind="image", in_channels=3, hidden=16, latent_dim=16,
                      codebook_size=256, n_res_blocks=1)
    data = make_images(key, n_clients * local_batch, size=16,
                       n_identities=C.N_IDENTITIES)
    stacked = partition_stacked(data, n_clients, regime="iid")
    rounds = 3 if C.QUICK else 5

    # one (C, local_batch, ...) stacked batch per round, materialized up
    # front so the timed windows measure the round, not host-side slicing
    round_xs = [jax.block_until_ready(b.x) for b in
                stacked_batches(stacked, local_batch, epochs=rounds + 1)]

    server = OC.server_init(key, cfg)
    for i in range(20 if C.QUICK else 60):
        sel = jax.random.randint(jax.random.fold_in(key, i), (32,), 0,
                                 data.x.shape[0])
        server, _ = OC.server_pretrain_step(server, cfg, data.x[sel])

    engine = SimEngine(cfg, lr=1e-4, gamma=0.99)
    clients = engine.init_clients(server, n_clients)

    clients, packed = engine.round(clients, round_xs[0])       # compile
    jax.block_until_ready(packed.payload)
    t0 = time.time()
    for xb in round_xs[1:]:
        clients, packed = engine.round(clients, xb)
        jax.block_until_ready(packed.payload)   # await each round's uplink,
    dt = time.time() - t0                       # same sync as the baseline

    # 1x baseline: the SAME work as a Python loop over single clients —
    # identical per-round batches, including the per-round pack
    step = jax.jit(lambda c, xb: OC.client_round(c, cfg, xb, lr=1e-4,
                                                 gamma=0.99))
    loop_clients = [OC.client_init(server) for _ in range(n_clients)]
    step(loop_clients[0], round_xs[0][0])                      # compile
    t0 = time.time()
    for xb in round_xs[1:]:
        loop_out = [step(c, xb[i]) for i, c in enumerate(loop_clients)]
        loop_clients = [o[0] for o in loop_out]
        loop_packed = pack_codes(jnp.stack([o[1] for o in loop_out]),
                                 bits=engine.bits)
        jax.block_until_ready(loop_packed)
    loop_dt = time.time() - t0

    _emit("sim", "n_clients", n_clients)
    _emit("sim", "round_ms", f"{dt / rounds * 1e3:.1f}")
    _emit("sim", "clients_per_sec", f"{n_clients * rounds / dt:.1f}",
          extra="python client loop is the 1x baseline")
    _emit("sim", "speedup_vs_loop", f"{loop_dt / dt:.1f}")
    naive = packed.count * 4
    _emit("sim", "bytes_per_round", packed.nbytes)
    _emit("sim", "bits_per_code", packed.bits)
    _emit("sim", "bytes_per_round_int32", naive)
    _emit("sim", "pack_ratio", f"{naive / packed.nbytes:.2f}")

    # Step 6: accumulate rounds server-side and train from the store
    from repro.core import downstream as DS
    store = CodeStore(cfg)
    for r, b in enumerate(stacked_batches(stacked, local_batch, epochs=3,
                                          seed=1)):
        clients, packed = engine.round(clients, b.x)
        store.add(packed, round=r, labels=b.content)
    server = engine.merge_into_server(server, clients)
    feats, label_dict = store.dataset(server)         # decode ONCE
    labels = label_dict["label"]
    probe = DS.init_linear_probe(key, int(feats[0].size),
                                 int(stacked.content.max()) + 1)
    probe = DS.sgd_train(key, DS.linear_probe, probe, feats, labels,
                         steps=C.PROBE_STEPS)
    acc = DS.accuracy(DS.linear_probe, probe, feats, labels)
    _emit("sim", "ingest_rounds", len(store))
    _emit("sim", "ingest_total_bytes", store.total_bytes)
    _emit("sim", "ingest_probe_acc", f"{acc:.4f}")

    # ---- cohort-streamed population scaling curve (§2.2, ROADMAP item 1)
    import numpy as np

    from repro.sim import CohortEngine, CohortPlan

    pcfg = DVQAEConfig(kind="image", in_channels=3, hidden=8, latent_dim=8,
                       codebook_size=256, n_res_blocks=1)
    pserver = OC.server_init(key, pcfg)
    ceng = CohortEngine(pcfg, gamma=0.99, n_local_steps=0)
    cohort_size = 256 if C.QUICK else 1024
    # smoke runs the 1k rung + parity assert only — the 10k/100k rungs
    # burn ~85 s of wall clock that CI doesn't need
    pop_sizes = [1024] if C.QUICK else [1024, 10240, 102400]
    pool = jax.block_until_ready(
        jax.random.normal(key, (4096, 1, 8, 8, 3)))    # shared sample pool

    def data_fn(ids):
        # slot-id-keyed batches WITHOUT materializing population data:
        # each client reads its own pool row, so any cohort grouping
        # sees identical per-client batches (the parity invariant)
        return pool[np.asarray(ids) % pool.shape[0]]

    # parity gate: the streamed round must reproduce the one-shot
    # population round bit-for-bit before any throughput is reported
    n_par = pop_sizes[0]
    full = ceng.round(pserver, CohortPlan.from_groups([np.arange(n_par)]),
                      data_fn)
    parts = ceng.round(pserver, CohortPlan.build(np.arange(n_par),
                                                 cohort_size), data_fn)
    from repro.wire import concat_payloads
    cat = concat_payloads(parts.payloads)
    parity = (np.array_equal(parts.stats.num, full.stats.num)
              and np.array_equal(parts.stats.den, full.stats.den)
              and np.array_equal(np.asarray(cat.payload),
                                 np.asarray(full.payloads[0].payload)))
    bytes_match = parts.nbytes == full.nbytes
    _emit("sim", "cohort_parity_bitexact", int(parity and bytes_match),
          extra="streamed round vs one-shot population round")
    assert parity and bytes_match, "cohort parity broken — curve invalid"
    _emit("sim", "cohort_parity_pop", n_par)
    _emit("sim", "cohort_size", cohort_size)

    for n_pop in pop_sizes:
        plan = CohortPlan.build(np.arange(n_pop), cohort_size)
        warm = CohortPlan.from_groups([plan.cohorts[0]])
        ceng.round(pserver, warm, data_fn)              # compile the shape
        t0 = time.time()
        out = ceng.round(pserver, plan, data_fn)
        dt = time.time() - t0
        _emit("sim", f"pop{n_pop}_clients_per_sec", f"{n_pop / dt:.0f}")
        _emit("sim", f"pop{n_pop}_round_s", f"{dt:.2f}")
        _emit("sim", f"pop{n_pop}_bytes", out.nbytes)
        _emit("sim", f"pop{n_pop}_cohorts", plan.n_cohorts)
    _emit("sim", "pop_max_clients", pop_sizes[-1])


# ---------------------------------------------------------------- server

def bench_server(key):
    """Scheduler-driven traffic (``CohortEngine.run_traffic`` into an
    ``OctopusServer``) across STANDARD_SCENARIOS: rounds/sec, measured
    uplink bytes, multi-task accuracy from one decode, and the decode
    amortization factor (schema in the module docstring)."""
    from repro.core import octopus as OC
    from repro.core.dvqae import DVQAEConfig
    from repro.data import make_images, partition_stacked
    from repro.launch.octopus_server import run_scenario
    from repro.server import STANDARD_SCENARIOS, MultiTaskTrainer, TaskSpec
    from repro.sim import CohortEngine

    n_slots = 8 if C.QUICK else 16
    local_b, rounds = 8, (4 if C.QUICK else 8)
    cfg = DVQAEConfig(kind="image", in_channels=3, hidden=16, latent_dim=16,
                      codebook_size=64, n_res_blocks=1)
    data = make_images(key, n_slots * local_b * 4, size=16,
                       n_identities=C.N_IDENTITIES)
    server, _ = OC.server_pretrain(key, OC.server_init(key, cfg), cfg,
                                   data.x, steps=20 if C.QUICK else 60)
    stacked = partition_stacked(data, n_slots, regime="skewed", skew=0.2)
    engine = CohortEngine(cfg, lr=1e-4, gamma=0.95)
    tasks = [TaskSpec("content", int(stacked.content.max()) + 1),
             TaskSpec("style", int(stacked.style.max()) + 1)]

    last = None
    for i, (name, sc) in enumerate(STANDARD_SCENARIOS.items()):
        run = run_scenario(
            name, sc, engine=engine, server=server, stacked=stacked,
            slots=n_slots, rounds=rounds, local_batch=local_b,
            probe_steps=C.PROBE_STEPS, key=key, index=i, verbose=False)
        delivered = run.queue.bytes_delivered
        _emit("server", f"{name}_rounds_per_sec",
              f"{run.rounds_per_sec:.2f}")
        _emit("server", f"{name}_participants", run.participants)
        _emit("server", f"{name}_bytes_delivered", delivered)
        _emit("server", f"{name}_bytes_sent", run.queue.bytes_sent,
              extra="incl. dropped / in-flight")
        _emit("server", f"{name}_store_records", len(run.wire.store),
              extra="v" + "+".join(map(str, run.wire.store.versions)))
        for t, a in run.acc.items():
            _emit("server", f"{name}_acc_{t}", f"{a:.4f}")
        _emit("server", f"{name}_bytes_per_point",
              f"{delivered / max(run.acc['content'], 1e-3):.0f}")
        last = run

    # decode amortization, measured end-to-end: training every head from
    # ONE shared decode vs a per-task pipeline that re-decodes the store
    # for each head (what Step 6 without the shared store would do).
    # Every trainer's jitted step is warmed first so the ratio measures
    # decode + train work, not compile-count asymmetry.
    steps = max(C.PROBE_STEPS // 4, 10)
    feats, labels = last.wire.features()
    in_dim = int(feats[0].size)
    shared = MultiTaskTrainer(key, tasks, in_dim)
    singles = [MultiTaskTrainer(key, [t], in_dim) for t in tasks]
    for tr in [shared] + singles:
        tr.fit(key, feats, labels, steps=1, batch=64)      # compile warmup
    t0 = time.time()
    feats, labels = last.wire.features()
    shared.fit(key, feats, labels, steps=steps, batch=64)
    t_shared = max(time.time() - t0, 1e-9)
    t0 = time.time()
    for tr in singles:
        feats, labels = last.wire.features()              # per-task decode
        tr.fit(key, feats, labels, steps=steps, batch=64)
    t_per_task = time.time() - t0
    _emit("server", "decode_amortization", f"{t_per_task / t_shared:.2f}",
          extra="per-task pipeline time / shared pipeline time")
    _emit("server", "decode_shared_pipeline_ms", f"{t_shared * 1e3:.0f}")

    # ---- continuous-ingest soak: the headline sustained-throughput row.
    # Open-ended Poisson traffic under churn drives the clocked service
    # through a sharded store with a deliberately tight admission window
    # (small queue capacity), so backpressure verdicts and a rolling
    # codebook migration are part of the measured steady state — the
    # uplinks/sec figure prices admission control in, not around.
    import numpy as np

    from repro.server import (BulkDecodePolicy, ContinuousIngestService,
                              RoundScheduler, SchedulerConfig,
                              ShardedCodeStore)
    from repro.sim import CohortEngine
    from repro.wire import OctopusServer

    n_ticks = 6 if C.QUICK else 20
    ccfg = DVQAEConfig(kind="image", in_channels=3, hidden=8, latent_dim=8,
                       codebook_size=64, n_res_blocks=1)
    cstate = OC.server_init(key, ccfg)
    srv = OctopusServer(cstate, ccfg,
                        store=ShardedCodeStore(ccfg, n_shards=4,
                                               capacity_samples=4096))
    svc = ContinuousIngestService(
        srv, capacity=2, defer_depth=1,
        decode_policy=BulkDecodePolicy(min_batch=1, max_batch=64))
    sched = RoundScheduler(
        n_slots * 2,
        SchedulerConfig(rate=float(n_slots), straggler_prob=0.4,
                        max_delay=2, drop_prob=0.1, leave_prob=0.2,
                        join_prob=0.5),
        key=jax.random.fold_in(key, 99))
    ceng = CohortEngine(ccfg, gamma=0.95, n_local_steps=0)
    pool = jax.block_until_ready(
        jax.random.normal(key, (256, 1, 8, 8, 3)))
    data_fn = lambda ids: pool[np.asarray(ids) % pool.shape[0]]

    # warm the per-cohort compile outside the timed window
    ceng.run_continuous(svc, sched, data_fn, cohort_size=4, n_ticks=1)
    t0 = time.time()
    hist = ceng.run_continuous(svc, sched, data_fn, cohort_size=4,
                               n_ticks=n_ticks, merge_every=3,
                               migration_policy="keep")
    svc.drain()
    dt = max(time.time() - t0, 1e-9)

    n_up = sum(svc.verdicts.values())
    _emit("server", "continuous_uplinks_per_sec", f"{n_up / dt:.1f}",
          extra="sustained, churn + backpressure + rolling migration")
    _emit("server", "continuous_ticks", n_ticks)
    _emit("server", "continuous_participants",
          sum(t.n_participants for t in hist))
    for v in ("accepted", "migrated", "deferred", "rejected"):
        _emit("server", f"admission_{v}", svc.verdicts.get(v, 0))
        _emit("server", f"admission_{v}_bytes", svc.verdict_bytes.get(v, 0))
    q = svc.queue
    assert q.bytes_sent == (q.bytes_delivered + q.bytes_dropped +
                            q.bytes_rejected + q.bytes_duplicate +
                            q.bytes_in_flight), \
        "uplink byte ledger leaked under backpressure"
    backpressured = (svc.verdicts.get("deferred", 0)
                     + svc.verdicts.get("rejected", 0))
    assert backpressured >= 1, \
        "soak never engaged backpressure — tighten capacity"
    _emit("server", "continuous_bytes_delivered", q.bytes_delivered)
    _emit("server", "continuous_bytes_refused",
          q.bytes_rejected + q.bytes_dropped,
          extra="still on the §2.8 ledger")
    _emit("server", "continuous_store_partitions",
          len(srv.store.partitions))
    _emit("server", "continuous_migrations", srv.registry.latest)
    _emit("server", "continuous_decode_amortization",
          f"{svc.decode_amortization:.2f}",
          extra="records decoded per fused dispatch")

    # ---- chaos plane: the same soak through a FaultyChannel, journaled.
    # goodput_under_faults prices retries, duplicates and CRC rejections
    # into the delivered-byte rate; recovery_time_s measures the crash
    # drill (snapshot + journal replay to the exact pre-kill state).
    import os
    import tempfile

    from repro.server import ServerPersistence
    from repro.sim import FaultPlan, FaultyChannel
    from repro.wire import RetryPolicy

    root = os.path.join(tempfile.mkdtemp(prefix="octopus_bench_"), "srv")
    fstate = OC.server_init(key, ccfg)
    fsrv = OctopusServer(fstate, ccfg,
                         store=ShardedCodeStore(ccfg, n_shards=4,
                                                capacity_samples=4096))
    fsvc = ContinuousIngestService(
        fsrv, capacity=4, defer_depth=3,
        decode_policy=BulkDecodePolicy(min_batch=1, max_batch=64),
        persist=ServerPersistence(root, snapshot_every=5))
    chan = FaultyChannel(
        fsvc,
        FaultPlan(drop=0.15, duplicate=0.15, reorder=0.2, delay=0.3,
                  corrupt=0.1, truncate=0.1),
        key=jax.random.fold_in(key, 123),
        retry=RetryPolicy(max_attempts=3))
    fsched = RoundScheduler(
        n_slots * 2,
        SchedulerConfig(rate=float(n_slots), straggler_prob=0.4,
                        max_delay=2, drop_prob=0.1),
        key=jax.random.fold_in(key, 124))
    ceng.run_continuous(chan, fsched, data_fn, cohort_size=4, n_ticks=1)
    t0 = time.time()
    ceng.run_continuous(chan, fsched, data_fn, cohort_size=4,
                        n_ticks=n_ticks, merge_every=3,
                        migration_policy="keep")
    chan.drain()
    dt = max(time.time() - t0, 1e-9)
    fq = fsvc.queue
    assert fq.bytes_sent == (fq.bytes_delivered + fq.bytes_dropped +
                             fq.bytes_rejected + fq.bytes_duplicate +
                             fq.bytes_in_flight), \
        "uplink byte ledger leaked under chaos"
    assert sum(chan.faults.values()) > 0, "fault plan never fired"
    _emit("server", "goodput_under_faults",
          f"{fq.bytes_delivered / dt:.0f}",
          extra=f"delivered B/s, {sum(chan.faults.values())} faults + "
                f"{chan.retries} retries priced in")
    _emit("server", "faults_injected", sum(chan.faults.values()),
          extra=", ".join(f"{k}={v}"
                          for k, v in sorted(chan.faults.items())))
    _emit("server", "fault_retries", chan.retries)

    t0 = time.time()
    recovered = ContinuousIngestService.recover(
        root, ccfg, OC.server_init(key, ccfg),
        capacity=4, defer_depth=3,
        decode_policy=BulkDecodePolicy(min_batch=1, max_batch=64))
    rec_s = time.time() - t0
    assert recovered.tick_idx == fsvc.tick_idx
    assert recovered.verdicts == fsvc.verdicts, \
        "recovered verdict histogram diverged"
    assert recovered.queue.bytes_sent == fq.bytes_sent
    _emit("server", "recovery_time_s", f"{rec_s:.3f}",
          extra=f"snapshot + journal replay to tick {recovered.tick_idx}")


# ---------------------------------------------------------------- decode

def bench_decode(key):
    """Step 6 ingest hot path: fused packed->feature decode
    (ops.decode_codes, one pass, no index/atom tensors in HBM) vs the
    unpack-then-dequantize baseline, both jitted (schema in the module
    docstring)."""
    import numpy as np
    from repro.core import octopus as OC
    from repro.core.dvqae import DVQAEConfig
    from repro.kernels import ops
    from repro.wire import CodePayload

    n_samples = 2_000 if C.QUICK else 20_000
    T = 64                                    # codes per sample
    cases = [
        ("vq_k256", DVQAEConfig(kind="image", latent_dim=16,
                                codebook_size=256)),
        ("gsvq_g16s4", DVQAEConfig(kind="image", latent_dim=16,
                                   codebook_size=64, n_groups=16,
                                   n_slices=4)),
    ]
    rng = np.random.default_rng(0)
    for name, cfg in cases:
        cb = jax.random.normal(key, (cfg.codebook_size, cfg.latent_dim))
        bits = OC.transmit_bits(cfg)
        gsvq = cfg.n_groups > 1 or cfg.n_slices > 1
        shape = (n_samples, T, cfg.n_slices) if gsvq else (n_samples, T)
        hi = cfg.n_groups if gsvq else cfg.codebook_size
        idx = jnp.asarray(rng.integers(0, hi, size=shape), jnp.int32)
        payload = jax.block_until_ready(ops.pack_codes(idx, bits=bits))
        packed = CodePayload(payload=payload, bits=bits, shape=shape)

        fused_fn = jax.jit(lambda w: OC.codes_to_features(
            None, cfg, CodePayload(payload=w, bits=bits, shape=shape),
            codebook=cb))
        base_fn = jax.jit(lambda w: OC.codes_to_features(
            None, cfg, ops.unpack_codes(w, bits=bits,
                                        count=packed.count).reshape(shape),
            codebook=cb))
        jax.block_until_ready(fused_fn(payload))          # compile
        jax.block_until_ready(base_fn(payload))

        def timeit(fn, n=3 if C.QUICK else 10):
            t0 = time.time()
            for _ in range(n):
                out = fn(payload)
            jax.block_until_ready(out)
            return (time.time() - t0) / n

        t_fused, t_base = timeit(fused_fn), timeit(base_fn)
        gb = packed.nbytes / 1e9
        _emit("decode", f"{name}_fused_samples_per_sec",
              f"{n_samples / t_fused:.0f}", extra=f"{bits}bits_per_code")
        _emit("decode", f"{name}_baseline_samples_per_sec",
              f"{n_samples / t_base:.0f}")
        _emit("decode", f"{name}_fused_gbps", f"{gb / t_fused:.4f}")
        _emit("decode", f"{name}_baseline_gbps", f"{gb / t_base:.4f}")
        _emit("decode", f"{name}_speedup", f"{t_base / t_fused:.2f}",
              extra=f"{t_fused * 1e3:.1f}ms_fused")
    _emit("decode", "interpret_mode", 1,
          extra="fused path timed in Pallas interpret mode on CPU; TPU "
                "timings require hardware (cf. kernels section)")


# ---------------------------------------------------------------- encode

def bench_encode(key):
    """Client uplink hot path (§2.2 Steps 3-5, §3.8 encode latency):
    single-encode round + fused quantize-pack-stats (ops.encode_codes)
    vs the seed pipeline — forward for the indices, forward + encode
    AGAIN for the EMA refresh, then separate quantize/pack/ema dispatches
    (schema in the module docstring)."""
    from repro.core import dvqae, ema as EMA, octopus as OC
    from repro.core.disentangle import instance_norm_latent
    from repro.core.dvqae import DVQAEConfig, forward
    from repro.kernels import ops
    from repro.wire import round_words

    B = 32 if C.QUICK else 128
    cases = [
        ("vq_k256", DVQAEConfig(kind="image", in_channels=3, hidden=32,
                                latent_dim=16, codebook_size=256,
                                n_res_blocks=1)),
        ("gsvq_g16s4", DVQAEConfig(kind="image", in_channels=3, hidden=32,
                                   latent_dim=16, codebook_size=64,
                                   n_groups=16, n_slices=4,
                                   n_res_blocks=1)),
    ]
    rounds = 3 if C.QUICK else 10
    for name, cfg in cases:
        bits = OC.transmit_bits(cfg)
        server = OC.server_init(key, cfg)
        client = OC.client_init(server)
        x = jax.random.normal(key, (B, 16, 16, 3))

        fused_fn = jax.jit(lambda c, x: round_words(
            c, cfg, x, n_local_steps=0))

        # the seed ran Steps 3-4 and Step 5 as separate entry points,
        # each re-deriving the same latents with its own network pass
        # (client_transmit: full forward; client_codebook_refresh:
        # forward + encode — XLA dedupes those two within the dispatch,
        # but not across the two dispatches)
        def legacy_transmit(client, x, cfg=cfg, bits=bits):
            idx = forward(client.params, cfg, x).latent.indices
            return ops.pack_codes(idx, bits=bits)

        def legacy_refresh(client, x, cfg=cfg):
            out = forward(client.params, cfg, x)
            z_e, _ = dvqae.encode(client.params, cfg, x)
            z = instance_norm_latent(z_e) if cfg.apply_in else z_e
            rep = out.latent.indices
            if cfg.n_groups > 1 or cfg.n_slices > 1:
                ng = cfg.codebook_size // cfg.n_groups
                rep = rep * ng + ng // 2
                z = jnp.broadcast_to(z[..., None, :],
                                     rep.shape + z.shape[-1:])
            return EMA.ema_update(client.ema, z, rep, gamma=0.99)

        t_jit, r_jit = jax.jit(legacy_transmit), jax.jit(legacy_refresh)

        def legacy_round(client, x):
            payload = t_jit(client, x)
            return r_jit(client, x).codebook, payload

        _, words = fused_fn(client, x)                         # compile
        jax.block_until_ready(words)
        _, payload = legacy_round(client, x)
        jax.block_until_ready(payload)
        assert words.nbytes == payload.nbytes                  # same uplink

        def timeit(fn):
            t0 = time.time()
            for _ in range(rounds):
                out = fn(client, x)
            jax.block_until_ready(out)   # BOTH outputs — the baseline's
            return (time.time() - t0) / rounds   # refresh is a 2nd dispatch

        # interleave and keep the min — single passes are noise-dominated
        # at smoke scale on a shared CPU
        t_fused = min(timeit(fused_fn) for _ in range(5))
        t_base = min(timeit(legacy_round) for _ in range(5))
        gb = words.size * words.dtype.itemsize / 1e9
        _emit("encode", f"{name}_fused_samples_per_sec",
              f"{B / t_fused:.0f}", extra=f"{bits}bits_per_code")
        _emit("encode", f"{name}_baseline_samples_per_sec",
              f"{B / t_base:.0f}")
        _emit("encode", f"{name}_fused_gbps", f"{gb / t_fused:.5f}")
        _emit("encode", f"{name}_baseline_gbps", f"{gb / t_base:.5f}")
        _emit("encode", f"{name}_speedup", f"{t_base / t_fused:.2f}",
              extra=f"{t_fused * 1e3:.1f}ms_fused")

    # acceptance: the round runs the encoder exactly ONCE (counted, not
    # inferred) — the seed path ran three network passes for the same z
    from repro.obs import dispatch_monitor
    cfg = cases[0][1]
    server = OC.server_init(key, cfg)
    client = OC.client_init(server)
    x = jax.random.normal(key, (4, 16, 16, 3))
    with dispatch_monitor() as counts:
        OC.client_round(client, cfg, x, n_local_steps=0)
    _emit("encode", "encoder_passes_per_round", counts.encoder_passes,
          extra="seed_path=3")
    _emit("encode", "oracle_fallback", 1,
          extra="off-TPU ops.encode_codes runs the jnp oracle "
                "(bit-identical words); Pallas-kernel timings require "
                "hardware")


# ------------------------------------------------------------------ wire

def bench_wire(key):
    """Unified wire protocol: the OctopusClient/OctopusServer facade
    round vs the pure ``round_words`` core it wraps — must be
    dispatch-count neutral and bit-identical (schema in the module
    docstring)."""
    import numpy as np

    from repro.core import octopus as OC
    from repro.core.dvqae import DVQAEConfig
    from repro.wire import OctopusServer, round_words

    B = 32 if C.QUICK else 128
    rounds = 3 if C.QUICK else 10
    cfg = DVQAEConfig(kind="image", in_channels=3, hidden=32, latent_dim=16,
                      codebook_size=256, n_res_blocks=1)
    server = OC.server_init(key, cfg)
    client0 = OC.client_init(server)
    x = jax.random.normal(key, (B, 16, 16, 3))

    facade_fn = jax.jit(lambda c, xb: round_words(c, cfg, xb,
                                                  n_local_steps=0))
    _, words = facade_fn(client0, x)                       # compile
    jax.block_until_ready(words)
    # the facade's CodePayload carries exactly the pure core's words
    srv = OctopusServer(server, cfg)
    cl = srv.deploy()
    payload = cl.round(x, finetune=0)
    assert np.array_equal(np.asarray(payload.payload), np.asarray(words))
    _emit("wire", "bit_identical_to_fused", "True")

    def timeit(fn):
        t0 = time.time()
        for _ in range(rounds):
            out = fn(client0, x)
        jax.block_until_ready(out)
        return (time.time() - t0) / rounds

    t_facade = min(timeit(facade_fn) for _ in range(5))
    _emit("wire", "facade_samples_per_sec", f"{B / t_facade:.0f}")

    # dispatch neutrality, COUNTED (not inferred): encoder passes and
    # fused encode dispatches of one un-jitted facade round vs the pure
    # core, through the supported monitor (obs.dispatch_monitor)
    from repro.obs import dispatch_monitor

    with dispatch_monitor() as fcounts:
        cl.round(x, finetune=0)
    fe, fk = fcounts.encoder_passes, fcounts.encode_dispatches
    with dispatch_monitor() as lcounts:
        round_words(client0, cfg, x, n_local_steps=0)
    le, lk = lcounts.encoder_passes, lcounts.encode_dispatches
    _emit("wire", "facade_encoder_passes", fe, extra=f"fused={le}")
    _emit("wire", "facade_encode_dispatches", fk, extra=f"fused={lk}")
    assert (fe, fk) == (le, lk) == (1, 1)

    # wire roundtrip: payload bytes are the single accounting end to end
    payload = cl.round(x, finetune=0)
    srv.ingest(payload)
    feats, _ = srv.features()
    _emit("wire", "payload_bytes", payload.nbytes,
          extra=f"{payload.bits}bits_per_code")
    _emit("wire", "store_bytes_match", str(srv.store.total_bytes
                                           == payload.nbytes))
    _emit("wire", "decoded_samples", feats.shape[0])


# ----------------------------------------------------------------- privacy

def bench_privacy(key):
    """Red-team sweep (repro.privacy): attack-advantage-vs-knob curves,
    the leaky-control teeth check, membership inference, and the
    oblivious-store parity + overhead rows. Deterministic in ``key``."""
    from repro import privacy as P
    for r in P.run_sweep(key, quick=C.QUICK):
        extra = " ".join(f"{k}={v}" for k, v in sorted(r["extra"].items())) \
            if r.get("extra") else ""
        _emit("privacy", r["name"], r["value"], extra)


SECTIONS = {
    "fig4": bench_fig4,
    "fig5": bench_fig5,
    "table1": bench_table1,
    "fig9": bench_fig9,
    "sec2_8": bench_sec2_8,
    "sec3_8": bench_sec3_8,
    "kernels": bench_kernels,
    "gsvq": bench_gsvq,
    "sim": bench_sim,
    "server": bench_server,
    "decode": bench_decode,
    "encode": bench_encode,
    "wire": bench_wire,
    "privacy": bench_privacy,
}


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", "--section", dest="only", default="",
                    help="comma-separated subset of sections")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke scale (same as OCTOPUS_BENCH_QUICK=1)")
    args = ap.parse_args()
    if args.smoke:
        C.set_quick()
    run = [s.strip() for s in args.only.split(",") if s.strip()] or \
        list(SECTIONS)
    key = jax.random.PRNGKey(0)
    print("section,name,value,extra")
    for name in run:
        t0 = time.time()
        SECTIONS[name](key)
        wall = time.time() - t0
        _emit(name, "_section_wall_s", f"{wall:.1f}")
        _write_artifact(name, wall)


if __name__ == "__main__":
    main()
