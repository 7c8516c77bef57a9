"""Continuous-ingest server runtime (the Step-6 refactor contracts).

What makes the clocked service a subsystem and not a queue wrapper:
  * admission control is STRUCTURED — every offer gets a verdict
    (accepted / migrated / deferred / rejected + reason), and the byte
    ledger stays conserved across all four: Σ sent == Σ delivered +
    Σ dropped + Σ rejected + Σ in flight (§2.8 includes refusals);
  * a rolling ``v_n -> v_{n+1}`` migration window ingests interleaved
    payloads of BOTH versions, and decode stays bit-identical to
    decoding each payload against its pinned registry snapshot — under
    every policy (keep / retire / reencode);
  * open-ended Poisson traffic (``SchedulerConfig.rate``) is
    deterministic under one PRNG key, quiet ticks included.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import octopus as OC
from repro.core.dvqae import DVQAEConfig
from repro.kernels.pack_bits import code_bits
from repro.obs import report as obs_report
from repro.server import (BulkDecodePolicy, ContinuousIngestService,
                          RoundScheduler, SchedulerConfig, ShardedCodeStore)
from repro.sim import CohortEngine
from repro.wire import WIRE_VERSION, CodePayload, OctopusServer

N_CLIENTS = 12


@pytest.fixture(autouse=True)
def no_ambient_recorder():
    obs.uninstall()
    yield
    obs.uninstall()


@pytest.fixture(scope="module")
def tiny_cfg():
    return DVQAEConfig(kind="image", in_channels=3, hidden=8, latent_dim=8,
                       codebook_size=16, n_res_blocks=1)


@pytest.fixture(scope="module")
def state(tiny_cfg):
    return OC.server_init(jax.random.PRNGKey(0), tiny_cfg)


@pytest.fixture(scope="module")
def data():
    return jax.random.normal(jax.random.PRNGKey(1),
                             (N_CLIENTS, 2, 8, 8, 3))


def _data_fn(data):
    return lambda ids: data[np.asarray(ids)]


def _pack(seed, version=0, c=2, b=3, t=4):
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, 16, size=(c, b, t))
    return CodePayload.pack(jnp.asarray(codes, jnp.int32),
                            bits=code_bits(16), version=version)


def _service(tiny_cfg, state, **kw):
    srv = OctopusServer(state, tiny_cfg,
                        store=ShardedCodeStore(tiny_cfg, n_shards=2))
    return ContinuousIngestService(srv, **kw)


# ------------------------------------------------------- admission control

def test_backpressure_verdicts_and_byte_conservation(tiny_cfg, state):
    """A bounded queue rejects past capacity and defers past
    defer_depth; every byte that hit the door is conserved across
    delivered / dropped / rejected / in-flight (§2.8 incl. refusals)."""
    svc = _service(tiny_cfg, state, capacity=2, defer_depth=1)
    verdicts = [svc.offer(_pack(i), client_ids=np.array([2 * i, 2 * i + 1]))
                for i in range(5)]
    assert [v.verdict for v in verdicts] == \
        ["accepted", "deferred", "rejected", "rejected", "rejected"]
    assert all(v.reason == "queue_full" for v in verdicts[2:])
    assert svc.n_rejected == 3 and svc.n_deferred == 1
    q = svc.queue
    # rejected payloads never queue, but their measured bytes ledger
    assert len(q) == 2
    assert q.bytes_sent == sum(v.nbytes for v in verdicts)
    assert q.bytes_rejected == sum(v.nbytes for v in verdicts[2:])
    assert q.bytes_sent == q.bytes_delivered + q.bytes_dropped + \
        q.bytes_rejected + q.bytes_duplicate + q.bytes_in_flight
    ts = svc.tick()
    assert ts.n_delivered == 2 and ts.queue_depth == 0
    assert q.bytes_sent == q.bytes_delivered + q.bytes_dropped + \
        q.bytes_rejected + q.bytes_duplicate + q.bytes_in_flight
    # both admitted payloads landed (deferred is admitted, just slower)
    assert len(svc.wire.store) == 2


def test_wire_violations_reject_with_reason_at_the_door(tiny_cfg, state):
    svc = _service(tiny_cfg, state, capacity=8)
    good = _pack(0)
    for bad, reason in [
            (good._replace(wire=WIRE_VERSION + 1), "wire_revision"),
            (good._replace(privatized=False), "unprivatized"),
            (good._replace(version=9), "unknown_version")]:
        res = svc.offer(bad)
        assert res.verdict == "rejected" and res.reason == reason
    res = svc.offer(good, dropped=True)     # radio loss burns the bytes
    assert res.verdict == "rejected" and res.reason == "radio_drop"
    assert svc.queue.bytes_dropped == good.nbytes
    assert len(svc.queue) == 0 and len(svc.wire.store) == 0


def test_straggler_delay_holds_payloads_across_ticks(tiny_cfg, state):
    svc = _service(tiny_cfg, state)
    svc.offer(_pack(0), delay=2)
    assert svc.tick().n_delivered == 0
    assert svc.tick().n_delivered == 0
    assert svc.tick().n_delivered == 1      # arrival tick = offer + delay
    assert svc.queue.bytes_in_flight == 0


def test_bulk_decode_policy_amortizes_dispatches(tiny_cfg, state):
    """Background decode batches freshly-stored records: same-version
    records share ONE fused dispatch, so amortization grows past 1."""
    svc = _service(tiny_cfg, state,
                   decode_policy=BulkDecodePolicy(min_batch=1, max_batch=8,
                                                  interval_ticks=1))
    for i in range(4):
        svc.offer(_pack(i), client_ids=np.array([0, 1]))
    svc.tick()
    assert svc.decoded_records == 4
    assert svc.decode_dispatches == 1       # one (version, bits) group
    assert svc.decode_amortization == 4.0
    # interval_ticks=0 turns the background decoder off
    off = _service(tiny_cfg, state,
                   decode_policy=BulkDecodePolicy(interval_ticks=0))
    off.offer(_pack(0))
    off.tick()
    assert off.decoded_records == 0


# --------------------------------------------------------------- migration

def _merge_new_version(srv):
    return srv.merge(jnp.stack([jnp.ones((16, 8))]),
                     jnp.stack([jnp.ones((16,))]))


@pytest.mark.parametrize("policy", ["keep", "retire", "reencode"])
def test_live_migration_decode_bit_identical_to_pinned_snapshots(
        tiny_cfg, state, policy):
    """THE migration acceptance contract: interleaved payloads of both
    window versions ingest concurrently, and after the window closes
    every stored record still decodes bit-identically to decoding its
    payload against the registry snapshot it was packed under."""
    srv = OctopusServer(state, tiny_cfg,
                        store=ShardedCodeStore(tiny_cfg, n_shards=2))
    payloads = {0: [_pack(i, version=0) for i in range(2)],
                1: [_pack(10 + i, version=1) for i in range(2)]}
    v1 = _merge_new_version(srv)
    assert v1 == 1
    win = srv.begin_migration(policy=policy)
    assert (win.src, win.dst) == (0, 1)
    # interleave: v0, v1, v0, v1 — both dictionaries live on the wire
    verdicts = []
    for p0, p1 in zip(payloads[0], payloads[1]):
        verdicts.append(srv.ingest(p0, client_ids=np.array([0, 1])))
        verdicts.append(srv.ingest(p1, client_ids=np.array([2, 3])))
    assert [v.verdict for v in verdicts] == \
        ["migrated", "accepted"] * 2
    prog = srv.migration_progress()
    assert prog["src_records"] == 2 and prog["dst_records"] == 2

    # pin the per-payload reference features BEFORE the window closes
    ref = {}
    for v, ps in payloads.items():
        for p in ps:
            f = OC.codes_to_features(None, tiny_cfg, p,
                                     codebook=srv.registry.get(v))
            ref[(v, p.payload.tobytes())] = np.asarray(
                f.reshape((-1,) + f.shape[2:]))

    done = srv.complete_migration()
    assert srv.registry.migration is None
    if policy == "keep":
        assert srv.store.versions == (0, 1)
        assert done["n_reencoded"] == 0
    elif policy == "retire":
        # src records evicted, ledgered, src version refused at the door
        assert srv.store.versions == (1,)
        assert srv.registry.is_retired(0)
        assert srv.store.evicted_bytes_by_version[0] == \
            sum(p.nbytes for p in payloads[0])
        late = srv.ingest(_pack(99, version=0))
        assert late.verdict == "rejected" and late.reason == \
            "retired_version"
    else:
        assert srv.store.versions == (1,)
        assert done["n_reencoded"] == 2
        assert len(srv.store) == 4          # 2 kept + 2 transcoded

    # every SURVIVING record decodes bit-identically to its pinned
    # snapshot — migration never re-decodes against the wrong table
    for rec in srv.store.records:
        k = (rec.version, rec.packed.payload.tobytes())
        if k in ref:        # original records (re-encoded ones are new)
            np.testing.assert_array_equal(
                np.asarray(srv.decode(rec.packed)), ref[k])
    # and the registry still decodes RETIRED versions for anyone who
    # pinned them (snapshots are never deleted)
    for p in payloads[0]:
        np.testing.assert_array_equal(
            np.asarray(srv.decode(p)), ref[(0, p.payload.tobytes())])


def test_reencode_transcodes_to_nearest_dst_atoms(tiny_cfg, state):
    """Re-encoded records carry dst-version indices whose atoms are the
    nearest dst atoms to the src-decoded features."""
    srv = OctopusServer(state, tiny_cfg)
    p0 = _pack(3, version=0)
    v1 = _merge_new_version(srv)
    srv.begin_migration(policy="reencode")
    srv.ingest(p0)
    srv.complete_migration()
    (rec,) = srv.store.records
    assert rec.version == v1
    feats = OC.codes_to_features(None, tiny_cfg, p0,
                                 codebook=srv.registry.get(0))
    cb = np.asarray(srv.registry.get(v1))
    want = np.argmin(((np.asarray(feats)[..., None, :] - cb) ** 2
                      ).sum(-1), axis=-1)
    np.testing.assert_array_equal(np.asarray(rec.packed.unpack()), want)


def test_migration_window_guards(tiny_cfg, state):
    srv = OctopusServer(state, tiny_cfg)
    with pytest.raises(KeyError):
        srv.begin_migration()               # only v0 exists
    _merge_new_version(srv)
    srv.begin_migration(policy="keep")
    with pytest.raises(ValueError, match="still open"):
        srv.begin_migration(policy="keep")
    srv.complete_migration()
    with pytest.raises(ValueError, match="no migration window"):
        srv.complete_migration()
    with pytest.raises(ValueError, match="latest"):
        srv.registry.retire(srv.registry.latest)


# --------------------------------------------------- continuous traffic

def test_run_continuous_traced_conserves_bytes(tiny_cfg, data, tmp_path):
    """Open-ended churny traffic through the service, traced: the §2.8
    check (incl. the refused-payload conservation identity) passes, and
    backpressure actually engaged (>= 1 deferred/rejected verdict)."""
    state = OC.server_init(jax.random.PRNGKey(0), tiny_cfg)
    srv = OctopusServer(state, tiny_cfg,
                        store=ShardedCodeStore(tiny_cfg, n_shards=2))
    svc = ContinuousIngestService(srv, capacity=2, defer_depth=1)
    sched = RoundScheduler(
        N_CLIENTS,
        SchedulerConfig(rate=6.0, straggler_prob=0.5, max_delay=2,
                        drop_prob=0.2, leave_prob=0.2, join_prob=0.5),
        key=jax.random.PRNGKey(7))
    engine = CohortEngine(tiny_cfg, gamma=0.9, n_local_steps=0)
    trace = tmp_path / "cont.jsonl"
    with obs.recording(trace):
        hist = engine.run_continuous(svc, sched, _data_fn(data),
                                     cohort_size=3, n_ticks=5,
                                     merge_every=2,
                                     migration_policy="keep")
        svc.drain()
    assert len(hist) == 5
    assert sum(t.n_rejected for t in hist) + \
        sum(t.n_deferred for t in hist) >= 1
    q = svc.queue
    assert q.bytes_sent == q.bytes_delivered + q.bytes_dropped + \
        q.bytes_rejected + q.bytes_duplicate + q.bytes_in_flight
    # merges happened and opened rolling windows
    assert any(t.merged_version for t in hist)
    summary = obs_report.summarize(obs_report.load_events(str(trace)))
    assert obs_report.check_bytes(summary) == []
    assert summary["admission"]["verdicts"]     # non-empty histogram
    assert summary["kinds"].get("migration", 0) >= 1


def test_run_continuous_deterministic(tiny_cfg, data):
    """Same key -> same verdict stream, byte ledger and merged
    dictionary — open-ended traffic is replayable."""
    def go():
        state = OC.server_init(jax.random.PRNGKey(0), tiny_cfg)
        srv = OctopusServer(state, tiny_cfg)
        svc = ContinuousIngestService(srv, capacity=3)
        sched = RoundScheduler(
            N_CLIENTS, SchedulerConfig(rate=5.0, straggler_prob=0.4),
            key=jax.random.PRNGKey(3))
        engine = CohortEngine(tiny_cfg, gamma=0.9, n_local_steps=0)
        hist = engine.run_continuous(svc, sched, _data_fn(data),
                                     cohort_size=3, n_ticks=4,
                                     merge_every=2)
        return hist, svc
    ha, sa = go()
    hb, sb = go()
    assert ha == hb
    assert sa.verdicts == sb.verdicts
    assert sa.queue.bytes_sent == sb.queue.bytes_sent
    np.testing.assert_array_equal(
        np.asarray(sa.wire.registry.current),
        np.asarray(sb.wire.registry.current))


def test_poisson_arrivals_deterministic_and_bursty():
    """rate-driven scheduling: deterministic under the key, open-ended
    (variable counts, quiet ticks allowed), isolated substream."""
    cfg = SchedulerConfig(rate=2.0)
    a = RoundScheduler(16, cfg, key=jax.random.PRNGKey(2))
    b = RoundScheduler(16, cfg, key=jax.random.PRNGKey(2))
    ka = [a.step().participants.size for _ in range(20)]
    kb = [b.step().participants.size for _ in range(20)]
    assert ka == kb
    assert len(set(ka)) > 1                 # actually varies
    assert max(ka) <= 16
    # turning stragglers on must not change the arrival counts (each
    # draw purpose owns a substream)
    c = RoundScheduler(16, SchedulerConfig(rate=2.0, straggler_prob=0.9),
                       key=jax.random.PRNGKey(2))
    kc = [c.step().participants.size for _ in range(20)]
    assert kc == ka
