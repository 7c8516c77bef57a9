#!/usr/bin/env python3
"""Bring-up smoke run of the OCTOPUS main path on a TPU.

    python chip_smoke.py              # one chip, every phase below
    python chip_smoke.py --chips 4    # ONLY the sharded population round
                                      # on 4 chips vs the same round on one

Everything runs in this one process, through the entry points a user
calls, at the full DVQ-AE width (the ``DVQAEConfig`` defaults: image
data, hidden 128, 2 residual blocks, latent 64, K=256, 8-bit codes) on
64x64x3 images made by ``repro.data.make_images`` from ``--seed``:

  step1       OctopusServer.init + pretrain; recon loss finite and falling
  steps2-5    8 clients deploy, finetune, round with labels through the
              compiled encode kernel
  ingest      those payloads offered to a ContinuousIngestService over a
              ShardedCodeStore with a BulkDecodePolicy, ticked until
              drained: every verdict accepted, the §2.8 byte ledger
              balances
  population  one CohortEngine.round over 1,024 clients in cohorts of 64
              (32 samples each), then the Step-5 merge_stats
  step6       srv.features() bulk decode + MultiTaskTrainer heads on
              content and style
  gsvq        one GSVQ client round (8 groups x 4 slices), encoded and
              decoded

Reference checks use the plain float32 jnp oracles of
``repro/kernels/ref.py`` under ``jax.default_matmul_precision("highest")``:
encode codes must equal the reference's argmin wherever its best and
second-best scores differ by more than ``NEAR_TIE`` (the mismatch count
is printed), and decoded features must equal a plain ``table[idx]``
gather exactly.

Each phase prints one line with its wall seconds and the XLA compile
seconds spent in it; the first failure raises and exits non-zero. The
last line of stdout is ``{"ok": true, "device": {...}}``. Without a TPU
the script refuses to run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Any, Dict, NamedTuple

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: score gap under which the reference's best and second-best codes count
#: as a near-tie. Scores are O(10-100) squared distances (VQ) or O(1-10)
#: group-mean distances (GSVQ); float32 rounding moves them by ~1e-5.
NEAR_TIE = 1e-3

N_STYLES = 10            # identities drawn by make_images (private label)
GSVQ = (8, 4)            # (n_groups, n_slices), as in tests/test_gsvq.py


class Sizes(NamedTuple):
    """How much work each phase does. Defaults: the full-width run."""
    cfg: Any = None                  # DVQAEConfig; None = the defaults
    image: int = 64
    atd_images: int = 256
    pretrain_steps: int = 10
    pretrain_batch: int = 32
    n_clients: int = 8
    per_client: int = 32
    pop_clients: int = 1024
    cohort: int = 64
    head_steps: int = 50
    head_batch: int = 64


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class Ctx:
    """State handed from phase to phase."""

    def __init__(self, sizes: Sizes, seed: int):
        import jax
        from repro.core.dvqae import DVQAEConfig
        self.sizes = sizes
        self.cfg = sizes.cfg if sizes.cfg is not None else DVQAEConfig()
        self.base = jax.random.PRNGKey(seed)
        self.srv = None
        self.clients = []
        self.batches = []
        self.payloads = []

    def key(self, name: str):
        import jax
        import zlib
        return jax.random.fold_in(self.base, zlib.crc32(name.encode()))

    def images(self, name: str, n: int):
        from repro.data import make_images
        return make_images(self.key(name), n, size=self.sizes.image,
                           n_identities=N_STYLES)


# ---------------------------------------------------------------- checks

def encode_check(cfg, z, codebook, payload) -> Dict[str, int]:
    """Kernel codes of ``payload`` vs the float32 reference argmin on the
    same latents ``z`` (B, T, M). Mismatches are allowed only where the
    reference's two best scores lie within NEAR_TIE."""
    import jax
    import numpy as np
    from repro.kernels.ref import encode_scores_ref
    zf = z.reshape(1, -1, z.shape[-1])
    with jax.default_matmul_precision("highest"):
        scores = encode_scores_ref(zf, codebook[None],
                                   n_groups=cfg.n_groups,
                                   n_slices=cfg.n_slices)
        neg, arg = jax.lax.top_k(-scores, 2)
    want = np.asarray(arg[..., 0]).reshape(-1)
    gap = np.asarray(neg[..., 0] - neg[..., 1]).reshape(-1)
    got = np.asarray(payload.unpack()).reshape(-1)
    check(got.shape == want.shape, f"code count {got.shape} vs {want.shape}")
    miss = got != want
    near = gap <= NEAR_TIE
    bad = int(np.sum(miss & ~near))
    check(bad == 0, f"{bad} encode codes differ from the reference outside "
                    f"near-ties (gap > {NEAR_TIE})")
    return {"codes": int(got.size), "mismatches": int(np.sum(miss)),
            "near_ties": int(np.sum(near))}


def decode_reference(cfg, codebook, payload):
    """Plain gather of the decode table at the payload's codes, in the
    store's (C*B, T, F) feature layout."""
    import jax.numpy as jnp
    from repro.core import octopus as OC
    table, n_slices = OC.decode_table(cfg, codebook)
    idx = payload.unpack()                               # (C, B, T[, S])
    if n_slices > 1:
        rows = table[jnp.arange(n_slices) * cfg.n_groups + idx]
        rows = rows.reshape(idx.shape[:-1] + (-1,))
    else:
        rows = table[idx]
    return rows.reshape((-1,) + rows.shape[2:])


def assert_equal(got, want, what: str) -> None:
    import numpy as np
    g, w = np.asarray(got), np.asarray(want)
    check(g.shape == w.shape, f"{what}: shape {g.shape} vs {w.shape}")
    n = int(np.sum(g != w))
    check(n == 0, f"{what}: {n} of {g.size} values differ from the "
                  f"plain gather")


def ledger_balances(queue) -> bool:
    return queue.bytes_sent == (queue.bytes_delivered + queue.bytes_dropped
                                + queue.bytes_rejected
                                + queue.bytes_duplicate
                                + queue.bytes_in_flight)


# ---------------------------------------------------------------- phases

def phase_step1(ctx: Ctx) -> str:
    """Step 1: ATD pretraining of the global DVQ-AE."""
    import numpy as np
    from repro.server import ShardedCodeStore
    from repro.wire import OctopusServer
    s, cfg = ctx.sizes, ctx.cfg
    atd = ctx.images("atd", s.atd_images)
    srv = OctopusServer.init(ctx.key("server"), cfg,
                             store=ShardedCodeStore(cfg, n_shards=4))
    losses = []
    for i in range(s.pretrain_steps):
        out = srv.pretrain(ctx.key(f"pretrain{i}"), atd.x, steps=1,
                           batch=s.pretrain_batch)
        losses.append(float(out.recon_loss))
    check(bool(np.all(np.isfinite(losses))), f"recon loss {losses}")
    check(losses[-1] < losses[0], f"recon loss did not fall: {losses}")
    ctx.srv = srv
    return (f"recon_loss {losses[0]:.6f} -> {losses[-1]:.6f} "
            f"over {len(losses)} steps")


def phase_clients(ctx: Ctx) -> str:
    """Steps 2-5: deploy, explicit fine-tune, then the fused uplink round
    with labels; each client's codes are checked against the reference
    on the very latents the round quantized."""
    from repro.core import octopus as OC
    s, cfg, srv = ctx.sizes, ctx.cfg, ctx.srv
    tot = {"codes": 0, "mismatches": 0, "near_ties": 0}
    for i in range(s.n_clients):
        d = ctx.images(f"client{i}", s.per_client)
        cl = srv.deploy(client_id=i)
        cl.finetune(d.x, steps=1)
        z, _ = OC.client_encode(cl.state.params, cfg, d.x)
        codebook = cl.codebook
        p = cl.round(d.x, labels={"content": d.content, "style": d.style},
                     finetune=0)
        for k, v in encode_check(cfg, z, codebook, p).items():
            tot[k] += v
        ctx.clients.append(cl)
        ctx.batches.append(d)
        ctx.payloads.append(p)
    nbytes = sum(p.nbytes for p in ctx.payloads)
    return (f"{s.n_clients} clients, {tot['codes']} codes, encode "
            f"mismatches {tot['mismatches']} (near-ties {tot['near_ties']},"
            f" tol {NEAR_TIE}), uplink {nbytes} B")


def phase_ingest(ctx: Ctx) -> str:
    """The payloads through the continuous ingest service until drained."""
    from repro.server import BulkDecodePolicy, ContinuousIngestService
    srv = ctx.srv
    svc = ContinuousIngestService(
        srv, decode_policy=BulkDecodePolicy(min_batch=2, max_batch=8,
                                            interval_ticks=1))
    results = [cl.send(svc, p) for cl, p in zip(ctx.clients, ctx.payloads)]
    ticks = svc.drain()
    verdicts = sorted({r.verdict for r in results})
    check(verdicts == ["accepted"], f"verdicts {verdicts}")
    q = svc.queue
    sent = sum(p.nbytes for p in ctx.payloads)
    check(ledger_balances(q), "byte ledger does not balance")
    check(q.bytes_sent == q.bytes_delivered == sent
          == srv.store.ingested_bytes and q.bytes_in_flight == 0,
          f"sent {q.bytes_sent} delivered {q.bytes_delivered} store "
          f"{srv.store.ingested_bytes} payloads {sent}")
    check(svc.decoded_records == len(ctx.payloads),
          f"background decode took {svc.decoded_records} records")
    return (f"{len(results)} accepted in {len(ticks)} ticks, ledger "
            f"sent={q.bytes_sent} delivered={q.bytes_delivered} "
            f"in_flight={q.bytes_in_flight}, {svc.decode_dispatches} "
            f"decode dispatch(es)")


def phase_population(ctx: Ctx) -> str:
    """One streamed population round, then the exact Step-5 merge."""
    import jax
    import numpy as np
    from repro.kernels.pack_bits import packing_dims
    from repro.sim import CohortEngine, CohortPlan
    s, cfg, srv = ctx.sizes, ctx.cfg, ctx.srv
    eng = CohortEngine(cfg)
    plan = CohortPlan.build(np.arange(s.pop_clients), s.cohort)

    def data_fn(ids):
        d = ctx.images(f"pop{int(ids[0])}", len(ids) * s.per_client)
        return d.x.reshape((len(ids), s.per_client) + d.x.shape[1:])

    before = np.asarray(srv.state.params["codebook"])
    v0 = srv.version
    out = eng.round(srv.state, plan, data_fn, version=v0)
    v1 = srv.merge_stats(out.stats)
    after = np.asarray(srv.state.params["codebook"])
    G, W = packing_dims(eng.bits)
    per_pos = s.image // 4
    per_client = -(-s.per_client * per_pos * per_pos // G) * W * 4
    check(out.n_clients == s.pop_clients, f"{out.n_clients} clients")
    check(out.nbytes == s.pop_clients * per_client,
          f"uplink {out.nbytes} B, expected {s.pop_clients * per_client}")
    check(v1 == v0 + 1, f"merge registered v{v1} after v{v0}")
    check(bool(np.all(np.isfinite(after))), "merged codebook not finite")
    check(not np.array_equal(after, before), "merge left the codebook")
    jax.block_until_ready(out.payloads[-1].payload)
    return (f"{out.n_clients} clients in {plan.n_cohorts} cohorts of "
            f"{s.cohort}, uplink {out.nbytes} B, merged to v{v1}")


def phase_step6(ctx: Ctx) -> str:
    """Bulk decode of the store (exact vs the plain gather) + heads."""
    import numpy as np
    from repro.data.synthetic import N_SHAPES
    from repro.server import MultiTaskTrainer, TaskSpec
    s, cfg, srv = ctx.sizes, ctx.cfg, ctx.srv
    feats, labels = srv.features()
    want = np.concatenate([
        np.asarray(decode_reference(cfg, srv.registry.get(r.version),
                                    r.packed))
        for r in srv.store.records])
    assert_equal(feats, want, "store features")
    trainer = MultiTaskTrainer(ctx.key("heads"),
                               [TaskSpec("content", N_SHAPES),
                                TaskSpec("style", N_STYLES)],
                               in_dim=int(np.prod(feats.shape[1:])))
    trainer.fit(ctx.key("fit"), feats, labels, steps=s.head_steps,
                batch=s.head_batch)
    acc = trainer.accuracy(feats, labels)
    check(all(np.isfinite(v) for v in acc.values()), f"accuracy {acc}")
    return (f"{feats.shape[0]} samples x {int(np.prod(feats.shape[1:]))} "
            f"features decoded exactly; train accuracy content "
            f"{acc['content']:.4f} style {acc['style']:.4f}")


def phase_gsvq(ctx: Ctx) -> str:
    """The GSVQ kernel mode: one client round, ingested and decoded."""
    from repro.core import octopus as OC
    from repro.wire import OctopusServer
    s = ctx.sizes
    n_groups, n_slices = GSVQ
    cfg = ctx.cfg.replace(n_groups=n_groups, n_slices=n_slices)
    srv = OctopusServer.init(ctx.key("gsvq-server"), cfg)
    d = ctx.images("gsvq-client", s.per_client)
    cl = srv.deploy(client_id=0)
    z, _ = OC.client_encode(cl.state.params, cfg, d.x)
    codebook = cl.codebook
    p = cl.round(d.x, labels={"content": d.content}, finetune=0)
    enc = encode_check(cfg, z, codebook, p)
    res = srv.ingest(p, client_ids=[0])
    check(res.verdict == "accepted", f"verdict {res}")
    feats, _ = srv.features()
    assert_equal(feats, decode_reference(cfg, srv.registry.get(p.version),
                                         p), "GSVQ features")
    return (f"{n_groups} groups x {n_slices} slices, {p.bits}-bit codes, "
            f"{enc['codes']} codes, encode mismatches {enc['mismatches']} "
            f"(near-ties {enc['near_ties']}), {feats.shape[0]} x "
            f"{feats.shape[-1]} features decoded exactly")


def phase_sharded(ctx: Ctx) -> str:
    """A SimEngine population round sharded over every device vs the
    same round on one device: identical words, client trees within the
    tolerances of tests/test_sim.py.

    The one-device round advances the same clients on the same data in
    shard-sized calls, the per-device batch of the sharded program: on a
    TPU, XLA picks different conv numerics for 64 and for 16 clients per
    program (bf16 passes accumulate in another order), which moves
    near-tie codes by itself. Holding the per-program batch fixed
    isolates what sharding does."""
    import jax
    import numpy as np
    from repro.core import octopus as OC
    from repro.launch.mesh import make_host_mesh
    from repro.sim import SimEngine
    s, cfg = ctx.sizes, ctx.cfg
    server = OC.server_init(ctx.key("sharded-server"), cfg)
    d = ctx.images("sharded", s.cohort * s.per_client)
    data = d.x.reshape((s.cohort, s.per_client) + d.x.shape[1:])
    mesh = make_host_mesh()
    n_dev = mesh.devices.size
    check(s.cohort % n_dev == 0, f"{s.cohort} clients over {n_dev} devices")
    per = s.cohort // n_dev
    plain = SimEngine(cfg)
    parts = [plain.round(plain.init_clients(server, per),
                         data[i * per:(i + 1) * per]) for i in range(n_dev)]
    c1 = jax.tree.map(lambda *xs: np.concatenate([np.asarray(x)
                                                  for x in xs]),
                      *[c for c, _ in parts])
    w1 = np.concatenate([np.asarray(p.payload) for _, p in parts])
    sharded = SimEngine(cfg, mesh=mesh)
    c2, p2 = sharded.round(sharded.init_clients(server, s.cohort), data)
    w2 = np.asarray(p2.payload)
    check(w1.shape == w2.shape, f"words {w1.shape} vs {w2.shape}")
    n_diff = int(np.sum(w1 != w2))
    check(n_diff == 0, f"{n_diff} of {w1.size} words differ")
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, np.asarray(b), rtol=1e-4, atol=5e-5), c1, c2)
    shards = {sh.device: sh.data.shape[0]
              for sh in c2.params["codebook"].addressable_shards}
    lines = []
    for dev in mesh.devices.flat:
        st = dev.memory_stats() or {}
        lines.append(f"{dev.id}:{shards.get(dev, 0)} clients/"
                     f"{st.get('bytes_in_use', 'n/a')} B")
    print("  per-device clients/bytes_in_use: " + " ".join(lines),
          flush=True)
    check(len(shards) == n_dev and all(n > 0 for n in shards.values()),
          f"client shards {shards}")
    return (f"{s.cohort} clients over {n_dev} devices vs one device in "
            f"calls of {per}: {w1.size} words identical, client trees "
            f"within rtol 1e-4 / atol 5e-5")


PHASES = (("step1", phase_step1), ("steps2-5", phase_clients),
          ("ingest", phase_ingest), ("population", phase_population),
          ("step6", phase_step6), ("gsvq", phase_gsvq))
SHARDED_PHASES = (("sharded", phase_sharded),)


def assert_kernels_compiled(ctx: Ctx) -> str:
    """On the chip: no kernel runs interpreted or as the jnp oracle, and
    the lowered client round and bulk decode call the Mosaic kernels."""
    import jax
    from repro.kernels import ops
    from repro.wire import round_words
    from repro.wire.codec import decode_rows
    check(not ops.interpret_mode(), "kernels would run in interpret mode")
    cl, d, p = ctx.clients[0], ctx.batches[0], ctx.payloads[0]
    rnd = jax.jit(round_words, static_argnums=1).lower(
        cl.state, ctx.cfg, d.x).as_text()
    table = ctx.srv.registry.get(p.version)
    dec = jax.jit(lambda words, t: decode_rows(p._replace(payload=words),
                                               t)).lower(p.payload,
                                                         table).as_text()
    for name, txt in (("client round", rnd), ("bulk decode", dec)):
        check("tpu_custom_call" in txt, f"no Mosaic kernel in the lowered "
                                        f"{name}")
    return "client round and bulk decode lower to tpu_custom_call"


# ------------------------------------------------------------------ driver

class CompileClock:
    """XLA compile seconds and persistent-cache hits, via jax.monitoring."""

    def __init__(self):
        from jax import monitoring
        self.seconds = 0.0
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def run_phases(ctx: Ctx, phases, clock=None) -> None:
    for name, fn in phases:
        c0 = (clock.seconds, clock.hits) if clock else (0.0, 0)
        t0 = time.perf_counter()
        try:
            note = fn(ctx)
        except Exception as e:
            print(f"phase {name} FAILED after "
                  f"{time.perf_counter() - t0:.3f}s: {e}", flush=True)
            raise
        wall = time.perf_counter() - t0
        comp = (f" compile_s={clock.seconds - c0[0]:.3f} "
                f"cache_hits={clock.hits - c0[1]}") if clock else ""
        print(f"phase {name} ok wall_s={wall:.3f}{comp} | {note}",
              flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded population round")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    dev = devices[0]
    print(f"device {dev.platform} {dev.device_kind} x{len(devices)}, "
          f"jax {jax.__version__}, compile cache {cache}", flush=True)

    clock = CompileClock()
    ctx = Ctx(Sizes(), args.seed)
    t0 = time.perf_counter()
    if args.chips == 4:
        run_phases(ctx, SHARDED_PHASES, clock)
    else:
        run_phases(ctx, PHASES, clock)
        print(f"kernels: {assert_kernels_compiled(ctx)}", flush=True)
    print(f"total wall_s={time.perf_counter() - t0:.3f} "
          f"compile_s={clock.seconds:.3f} cache_hits={clock.hits}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
