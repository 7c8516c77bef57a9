"""Cohort-sharded population engine: scale-invariance property suite.

The cohort engine's whole correctness story is algebraic, so these tests
pin it BIT-EXACTLY (``array_equal``, no tolerance anywhere):

  * grouping invariance — any partition/order of the same client set,
    merged cohort-by-cohort, bit-matches the single full-population
    merge (codebooks, EMA merge stats, decoded features);
  * §2.8 byte accounting — Σ per-cohort ``CodePayload.nbytes`` equals
    the whole-population round's measured bytes (per-client padding
    included), for VQ and GSVQ across packing widths 1-12;
  * payload concatenation — stacking cohort payload words IS the
    population payload.

hypothesis widens the fixed cases to arbitrary partitions when it is
installed (requirements-dev.txt); the deterministic cases always run.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import octopus as OC
from repro.core.dvqae import DVQAEConfig
from repro.core.ema import (merge_codebook, merge_stats, merge_stats_add,
                            merge_stats_zero)
from repro.sim import CohortEngine, CohortPlan
from repro.wire import CodePayload, OctopusServer, concat_payloads

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:            # dev-only dependency; fixed cases still run
    HAVE_HYPOTHESIS = False

N_CLIENTS = 12


@pytest.fixture(scope="module")
def tiny_cfg():
    return DVQAEConfig(kind="image", in_channels=3, hidden=8, latent_dim=8,
                       codebook_size=16, n_res_blocks=1)


@pytest.fixture(scope="module")
def gsvq_cfg():
    return DVQAEConfig(kind="image", in_channels=3, hidden=8, latent_dim=8,
                       codebook_size=16, n_groups=4, n_slices=2,
                       n_res_blocks=1)


@pytest.fixture(scope="module")
def server(tiny_cfg):
    return OC.server_init(jax.random.PRNGKey(0), tiny_cfg)


@pytest.fixture(scope="module")
def gsvq_server(gsvq_cfg):
    return OC.server_init(jax.random.PRNGKey(0), gsvq_cfg)


@pytest.fixture(scope="module")
def data():
    return jax.random.normal(jax.random.PRNGKey(1),
                             (N_CLIENTS, 2, 8, 8, 3))


def _data_fn(data):
    return lambda ids: data[np.asarray(ids)]


def _partitions():
    """Order-preserving partitions of range(N_CLIENTS) into multi-client
    cohorts (the engine-level bit-invariance boundary — XLA specializes
    the degenerate C == 1 batch into a different program; singleton
    grouping is covered at the stats-algebra level, where the merge is
    exact for ANY grouping)."""
    ids = np.arange(N_CLIENTS)
    return [
        [ids],                                     # the population itself
        [ids[:5], ids[5:9], ids[9:]],              # ragged cohorts
        [ids[i:i + 2] for i in range(0, N_CLIENTS, 2)],   # minimal (C=2)
        [ids[:6], ids[6:]],                        # two halves
        [ids[:2], ids[2:5], ids[5:]],              # mixed 2/3/7
    ]


# -------------------------------------------------- grouping invariance

def _run(engine, server, groups, data):
    return engine.round(server, CohortPlan.from_groups(groups),
                        _data_fn(data))


@pytest.mark.parametrize("cfg_name", ["tiny_cfg", "gsvq_cfg"])
def test_cohort_grouping_invariance_bitexact(cfg_name, request, data):
    """Any order-preserving cohort partition reproduces the single
    full-population round bit-for-bit: merge stats, merged codebook,
    payload words, Σ bytes, decoded features."""
    cfg = request.getfixturevalue(cfg_name)
    srv = OC.server_init(jax.random.PRNGKey(0), cfg)
    engine = CohortEngine(cfg, gamma=0.9, n_local_steps=1)
    runs = [_run(engine, srv, g, data) for g in _partitions()]
    full = runs[0]
    full_payload = full.payloads[0]
    merged_full = OC.server_merge_stats(srv, full.stats)
    feats_full = OC.codes_to_features(srv, cfg, full_payload)
    for out in runs[1:]:
        np.testing.assert_array_equal(out.stats.num, full.stats.num)
        np.testing.assert_array_equal(out.stats.den, full.stats.den)
        merged = OC.server_merge_stats(srv, out.stats)
        np.testing.assert_array_equal(
            np.asarray(merged.params["codebook"]),
            np.asarray(merged_full.params["codebook"]))
        cat = concat_payloads(out.payloads)
        np.testing.assert_array_equal(np.asarray(cat.payload),
                                      np.asarray(full_payload.payload))
        assert cat.shape == full_payload.shape
        assert out.nbytes == full.nbytes == cat.nbytes
        feats = OC.codes_to_features(srv, cfg, cat)
        np.testing.assert_array_equal(np.asarray(feats),
                                      np.asarray(feats_full))


def test_cohort_order_invariance_of_merge(tiny_cfg, server, data):
    """Merge stats are COMMUTATIVE too: streaming the same cohorts in a
    different order bit-matches (payload order differs, the merge
    doesn't)."""
    engine = CohortEngine(tiny_cfg, gamma=0.9, n_local_steps=0)
    ids = np.arange(N_CLIENTS)
    fwd = _run(engine, server, [ids[:4], ids[4:8], ids[8:]], data)
    rev = _run(engine, server, [ids[8:], ids[4:8], ids[:4]], data)
    np.testing.assert_array_equal(fwd.stats.num, rev.stats.num)
    np.testing.assert_array_equal(fwd.stats.den, rev.stats.den)
    assert fwd.nbytes == rev.nbytes


if HAVE_HYPOTHESIS:
    @given(cuts=st.sets(st.integers(1, N_CLIENTS - 1), max_size=6),
           order_seed=st.integers(0, 2**31 - 1))
    @settings(max_examples=15, deadline=None)
    def test_cohort_merge_associativity_hypothesis(cuts, order_seed,
                                                   cached_round):
        """ARBITRARY partitions + cohort orders of one engine round
        bit-match the full-population merge. Per-client stats come from
        one cached engine round (cohorting is pure regrouping, as
        test_cohort_grouping_invariance_bitexact pins), so hypothesis
        explores partitions without recompiling the engine."""
        cbs, counts, full_stats = cached_round
        bounds = [0] + sorted(cuts) + [N_CLIENTS]
        groups = [np.arange(a, b) for a, b in zip(bounds, bounds[1:])]
        rng = np.random.default_rng(order_seed)
        acc = merge_stats_zero(*cbs.shape[1:])
        for g in rng.permutation(len(groups)):
            members = groups[g]
            acc = merge_stats_add(acc, merge_stats(cbs[members],
                                                   counts[members]))
        np.testing.assert_array_equal(acc.num, full_stats.num)
        np.testing.assert_array_equal(acc.den, full_stats.den)

    @pytest.fixture(scope="module")
    def cached_round(tiny_cfg, server, data):
        engine = CohortEngine(tiny_cfg, gamma=0.9, n_local_steps=0)
        out = _run(engine, server, [np.arange(N_CLIENTS)], data)
        eng = engine.engine
        clients, _ = eng.round(eng.init_clients(server, N_CLIENTS), data)
        cbs = np.asarray(clients.params["codebook"])
        counts = np.asarray(clients.ema.counts)
        return cbs, counts, out.stats


# ------------------------------------------------- merge-stats algebra

def test_cohort_plan_folds_singleton_tail():
    """13 members at cohort_size 4 -> (4, 4, 5), never a C=1 cohort
    (the degenerate vmap batch compiles into a different program)."""
    plan = CohortPlan.build(np.arange(13), 4)
    assert plan.sizes == (4, 4, 5)
    np.testing.assert_array_equal(plan.members, np.arange(13))
    assert CohortPlan.build(np.arange(1), 4).sizes == (1,)   # lone client
    assert CohortPlan.build(np.arange(12), 4).sizes == (4, 4, 4)


def test_merge_stats_singleton_grouping_is_exact(tiny_cfg, server, data):
    """At the stats level the merge IS exact for singleton grouping: one
    engine round's per-client stats, merged client-by-client, bit-match
    the full-population merge (the engine-level C >= 2 boundary is about
    XLA batch specialization, not the algebra)."""
    engine = CohortEngine(tiny_cfg, gamma=0.9, n_local_steps=0)
    eng = engine.engine
    clients, _ = eng.round(eng.init_clients(server, N_CLIENTS), data)
    cbs = np.asarray(clients.params["codebook"])
    counts = np.asarray(clients.ema.counts)
    full = merge_stats(cbs, counts)
    acc = merge_stats_zero(*cbs.shape[1:])
    for i in range(N_CLIENTS):
        acc = merge_stats_add(acc, merge_stats(cbs[i], counts[i]))
    np.testing.assert_array_equal(acc.num, full.num)
    np.testing.assert_array_equal(acc.den, full.den)


def test_merge_stats_zero_is_identity():
    s = merge_stats(np.random.default_rng(0).normal(size=(3, 8, 4)),
                    np.random.default_rng(1).random((3, 8)))
    z = merge_stats_zero(8, 4)
    np.testing.assert_array_equal(merge_stats_add(s, z).num, s.num)
    np.testing.assert_array_equal(merge_stats_add(z, s).den, s.den)


def test_merge_codebook_dead_atoms_keep_current():
    cur = np.arange(8, dtype=np.float32).reshape(4, 2)
    s = merge_stats(np.ones((1, 4, 2), np.float32),
                    np.array([[2.0, 0.0, 1.0, 0.0]]))
    out = merge_codebook(s, cur)
    np.testing.assert_array_equal(out[1], cur[1])
    np.testing.assert_array_equal(out[3], cur[3])
    np.testing.assert_array_equal(out[0], np.ones(2, np.float32))


def test_server_merge_stats_matches_weighted_average(tiny_cfg, server):
    """The fixed-point merge lands on the float count-weighted average
    (within fixed-point resolution) and keeps dtype/shape."""
    rng = np.random.default_rng(3)
    C, (K, M) = 5, server.params["codebook"].shape
    cbs = rng.normal(size=(C, K, M)).astype(np.float32)
    counts = rng.random((C, K)).astype(np.float32) + 0.1
    got = OC.server_merge_stats(
        server, merge_stats(cbs, counts)).params["codebook"]
    want = OC.server_merge_codebooks(server, cbs, counts).params["codebook"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert got.dtype == server.params["codebook"].dtype


# -------------------------------------------- §2.8 byte accounting

@pytest.mark.parametrize("bits", list(range(1, 13)))
def test_byte_accounting_cohort_invariance_bits(bits):
    """Σ per-cohort nbytes == whole-population nbytes for every packing
    width 1-12, per-client padding included — and the concatenated
    cohort words ARE the population words."""
    rng = np.random.default_rng(bits)
    idx = jnp.asarray(rng.integers(0, 1 << bits, size=(N_CLIENTS, 7)),
                      jnp.int32)
    full = CodePayload.pack_records(idx, bits=bits)
    for groups in _partitions()[1:]:
        parts = [CodePayload.pack_records(idx[jnp.asarray(g)], bits=bits)
                 for g in groups]
        assert sum(p.nbytes for p in parts) == full.nbytes
        cat = concat_payloads(parts)
        np.testing.assert_array_equal(np.asarray(cat.payload),
                                      np.asarray(full.payload))
        np.testing.assert_array_equal(np.asarray(cat.unpack()),
                                      np.asarray(full.unpack()))


@pytest.mark.parametrize("cfg_name", ["tiny_cfg", "gsvq_cfg"])
def test_round_bytes_cohort_invariant(cfg_name, request, data):
    """Engine-level: a cohorted round charges exactly the bytes of the
    whole-population round, for VQ and GSVQ wire formats."""
    cfg = request.getfixturevalue(cfg_name)
    srv = OC.server_init(jax.random.PRNGKey(0), cfg)
    engine = CohortEngine(cfg, gamma=0.9, n_local_steps=0)
    full = _run(engine, srv, [np.arange(N_CLIENTS)], data)
    parts = _run(engine, srv,
                 [np.arange(0, 5), np.arange(5, 9), np.arange(9, 12)], data)
    assert parts.nbytes == full.nbytes
    assert sum(p.nbytes for p in parts.payloads) == full.payloads[0].nbytes


# ------------------------------------------------- wire integration

def test_cohort_payloads_ingest_and_decode_like_population(tiny_cfg, data):
    """Per-cohort payloads through OctopusServer.ingest decode to the
    SAME feature rows as the single population payload."""
    state = OC.server_init(jax.random.PRNGKey(0), tiny_cfg)
    engine = CohortEngine(tiny_cfg, gamma=0.9, n_local_steps=0)
    full = _run(engine, state, [np.arange(N_CLIENTS)], data)
    parts = _run(engine, state,
                 [np.arange(0, 3), np.arange(3, 10), np.arange(10, 12)],
                 data)
    wire_a = OctopusServer(state, tiny_cfg)
    wire_a.ingest(full.payloads[0])
    wire_b = OctopusServer(state, tiny_cfg)
    for p in parts.payloads:
        wire_b.ingest(p)
    fa, _ = wire_a.features()
    fb, _ = wire_b.features()
    np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))
    assert wire_b.store.total_bytes == wire_a.store.total_bytes


def test_traffic_run_is_replayable(tiny_cfg, data):
    """Two scheduler-driven traffic runs from the same key produce the
    identical byte ledger, store contents, and merged dictionaries."""
    from repro.server import RoundScheduler, SchedulerConfig

    def go():
        state = OC.server_init(jax.random.PRNGKey(0), tiny_cfg)
        wire = OctopusServer(state, tiny_cfg)
        sched = RoundScheduler(
            N_CLIENTS, SchedulerConfig(participation=0.5,
                                       straggler_prob=0.4, drop_prob=0.2),
            key=jax.random.PRNGKey(11))
        engine = CohortEngine(tiny_cfg, gamma=0.9, n_local_steps=0)
        hist = engine.run_traffic(wire, sched, _data_fn(data),
                                  cohort_size=3, n_rounds=4, merge_every=2)
        return wire, hist

    wa, ha = go()
    wb, hb = go()
    assert ha == hb
    np.testing.assert_array_equal(np.asarray(wa.registry.current),
                                  np.asarray(wb.registry.current))
    assert wa.store.total_bytes == wb.store.total_bytes
    fa, _ = wa.features()
    fb, _ = wb.features()
    np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))


def test_traffic_churn_versions_and_accounting(tiny_cfg, data):
    """The churn scenario through run_traffic: records land under >= 2
    codebook versions, the byte ledger closes, and every record
    re-decodes bit-exactly against its own version after all merges."""
    from repro.server import STANDARD_SCENARIOS, RoundScheduler, UplinkQueue
    n_rounds = 8
    state = OC.server_init(jax.random.PRNGKey(0), tiny_cfg)
    wire, queue = OctopusServer(state, tiny_cfg), UplinkQueue()
    sched = RoundScheduler(N_CLIENTS, STANDARD_SCENARIOS["churn"].sched,
                           key=jax.random.PRNGKey(3))
    sent_under = {}             # round -> (version, codebook) it packed under

    def data_fn(ids):
        sent_under.setdefault(sched.round - 1, (
            wire.version, np.asarray(wire.registry.current)))
        return data[np.asarray(ids)]

    def labels_fn(ids):
        return {"content": jnp.tile(jnp.arange(data.shape[1]),
                                    (len(ids), 1))}

    engine = CohortEngine(tiny_cfg, gamma=0.9, n_local_steps=0)
    hist = engine.run_traffic(wire, sched, data_fn, labels_fn=labels_fn,
                              cohort_size=4, n_rounds=n_rounds,
                              merge_every=2, queue=queue)

    merged = [h.merged_version for h in hist if h.merged_version is not None]
    assert merged == list(range(1, n_rounds // 2 + 1))
    assert wire.registry.latest == len(merged)
    assert queue.bytes_sent == (queue.bytes_delivered + queue.bytes_dropped
                                + queue.bytes_in_flight)
    assert queue.bytes_rejected == queue.bytes_duplicate == 0
    records = wire.store.records
    assert len({rec.version for rec in records}) >= 2  # stragglers lag

    for rec in records:
        version, codebook = sent_under[rec.round]
        assert rec.version == version
        codes = rec.packed.unpack().reshape((-1,) + rec.packed.shape[2:])
        now = OC.codes_to_features(None, tiny_cfg, codes,
                                   codebook=wire.registry.get(rec.version))
        then = OC.codes_to_features(None, tiny_cfg, codes,
                                    codebook=jnp.asarray(codebook))
        np.testing.assert_array_equal(np.asarray(now), np.asarray(then))

    feats, got = wire.features()
    assert feats.shape[0] == wire.store.n_samples
    assert got["content"].shape[0] == wire.store.n_samples
