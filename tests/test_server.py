"""Code-server subsystem (repro.server): store, registry, scheduler,
heads.

The contracts that make Step 6 a subsystem instead of a buffer:
  * CodeStore bounds memory (FIFO/reservoir eviction) and decodes each
    record bit-exactly against the codebook version it was packed under,
    no matter how many Step 5 merges happened since;
  * RoundScheduler is a pure function of its PRNG key (same key -> same
    participation/straggler/churn stream);
  * MultiTaskTrainer with one task IS core.downstream.sgd_train (exact
    same batch draws and AdamW math), so multi-head training is a strict
    generalization of the single-task path.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import downstream as DS
from repro.core import octopus as OC
from repro.core.dvqae import DVQAEConfig
from repro.kernels.pack_bits import code_bits
from repro.server import (CodeStore, CodebookRegistry, MultiTaskTrainer,
                          RoundScheduler, SchedulerConfig, TaskSpec)
from repro.wire import CodePayload


@pytest.fixture(scope="module")
def tiny_cfg():
    return DVQAEConfig(kind="image", in_channels=3, hidden=8, latent_dim=8,
                       codebook_size=16, n_res_blocks=1)


@pytest.fixture(scope="module")
def server(tiny_cfg):
    return OC.server_init(jax.random.PRNGKey(0), tiny_cfg)


def _pack(codes, version=0):
    """int32 (C, B, T) codes -> CodePayload like the engine emits."""
    return CodePayload.pack(jnp.asarray(codes, jnp.int32),
                            bits=code_bits(16), version=version)


def _codes(seed, c=2, b=3, t=4):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 16, size=(c, b, t))


# --------------------------------------------------------------- CodeStore

def test_store_add_validates_shapes(tiny_cfg):
    store = CodeStore(tiny_cfg)
    packed = _pack(_codes(0))
    with pytest.raises(ValueError, match="labels"):
        store.add(packed, labels=jnp.zeros((5,), jnp.int32))   # 5 != 2*3
    with pytest.raises(ValueError, match="client_ids"):
        store.add(packed, client_ids=np.arange(3))             # 3 != C=2
    store.add(packed, labels=jnp.zeros((2, 3), jnp.int32))     # (C, B) ok
    assert store.n_samples == 6


def test_store_fifo_eviction_keeps_freshest_window(tiny_cfg):
    store = CodeStore(tiny_cfg, capacity_samples=18, policy="fifo")
    for r in range(5):
        store.add(_pack(_codes(r)), round=r)
    assert store.n_samples <= 18
    assert [rec.round for rec in store.records] == [2, 3, 4]
    assert store.evicted_records == 2
    assert store.evicted_samples == 12


def test_store_reservoir_eviction_is_bounded_and_deterministic(tiny_cfg):
    def run(seed):
        store = CodeStore(tiny_cfg, capacity_samples=18, policy="reservoir",
                          seed=seed)
        for r in range(30):
            store.add(_pack(_codes(r)), round=r)
        return [rec.round for rec in store.records]

    kept = run(7)
    assert len(kept) == 3
    assert kept == run(7)                      # seeded -> deterministic
    # algorithm-R keeps an approx-uniform sample of history, not a FIFO
    # tail: across a few seeds, early records survive
    assert any(min(run(s)) < 20 for s in range(5))


def test_store_version_correct_decode_roundtrip(tiny_cfg, key):
    """Codes packed under version v decode bit-exactly against v's
    snapshot after later merges moved the registry on."""
    k1, k2 = jax.random.split(key)
    registry = CodebookRegistry(jax.random.normal(k1, (16, 8)))
    store = CodeStore(tiny_cfg)
    c0 = _codes(0)
    store.add(_pack(c0), round=0, version=0,
              labels={"content": jnp.zeros((2, 3), jnp.int32)})
    ref0 = np.asarray(registry.get(0))[np.asarray(c0).reshape(6, 4)]

    # two merges: the registry's latest table moves twice
    registry.register(jax.random.normal(k2, (16, 8)))
    registry.register(jax.random.normal(jax.random.fold_in(k2, 1), (16, 8)))
    c2 = _codes(2)
    store.add(_pack(c2), round=1, version=2,
              labels={"content": jnp.ones((2, 3), jnp.int32)})
    ref2 = np.asarray(registry.get(2))[np.asarray(c2).reshape(6, 4)]

    assert store.versions == (0, 2)
    feats, labels = store.dataset(None, registry=registry)
    np.testing.assert_array_equal(np.asarray(feats[:6]), ref0)   # NOT latest
    np.testing.assert_array_equal(np.asarray(feats[6:]), ref2)
    np.testing.assert_array_equal(np.asarray(labels["content"]),
                                  [0, 0, 0, 0, 0, 0, 1, 1, 1, 1, 1, 1])
    # keyed lookup: (client_id, round) -> that client's codes + version
    idx, version = store.get(1, 0)
    np.testing.assert_array_equal(np.asarray(idx), c0[1])
    assert version == 0


def test_store_bulk_decode_matches_current_codebook_path(tiny_cfg, server,
                                                         key):
    """Without a registry, dataset() == decoding everything against the
    server's current table (the old IngestBuffer behaviour)."""
    store = CodeStore(tiny_cfg)
    for r in range(3):
        store.add(_pack(_codes(r)), round=r, version=r)   # versions differ
    feats, _ = store.dataset(server)
    ref = OC.codes_to_features(server, tiny_cfg, store.codes())
    np.testing.assert_array_equal(np.asarray(feats), np.asarray(ref))


# ---------------------------------------------------------- staleness merge

def test_staleness_weighted_merge_discounts_stale_clients(server):
    cbs = jnp.stack([jnp.ones((16, 8)), 3.0 * jnp.ones((16, 8))])
    cts = jnp.ones((2, 16))
    even = OC.server_merge_codebooks(server, cbs, cts)
    np.testing.assert_allclose(np.asarray(even.params["codebook"]), 2.0,
                               rtol=1e-6)
    # client 1 is two versions stale at decay 0.5 -> weight 1 vs 0.25
    m = OC.server_merge_codebooks(server, cbs, cts,
                                  staleness=jnp.array([0, 2]),
                                  staleness_decay=0.5)
    np.testing.assert_allclose(np.asarray(m.params["codebook"]),
                               (1.0 + 0.25 * 3.0) / 1.25, rtol=1e-6)
    # decay 0 silences stale clients entirely
    reg = CodebookRegistry(server.params["codebook"])
    reg.register(server.params["codebook"])
    merged, v = reg.merge(server, cbs, cts, client_versions=np.array([1, 0]),
                          staleness_decay=0.0)
    assert v == 2 and v == reg.latest
    np.testing.assert_allclose(np.asarray(merged.params["codebook"]), 1.0,
                               rtol=1e-6)


def test_merge_with_zero_total_weight_keeps_current_dictionary(server):
    """If every client's contribution decays to zero (all fully stale),
    the merge must keep the current dictionary, not zero it out."""
    cbs = jnp.stack([jnp.ones((16, 8)), 3.0 * jnp.ones((16, 8))])
    cts = jnp.ones((2, 16))
    m = OC.server_merge_codebooks(server, cbs, cts,
                                  staleness=jnp.array([1, 2]),
                                  staleness_decay=0.0)
    np.testing.assert_array_equal(np.asarray(m.params["codebook"]),
                                  np.asarray(server.params["codebook"]))


# --------------------------------------------------------------- scheduler

def test_scheduler_deterministic_under_fixed_key():
    cfg = SchedulerConfig(participation=0.5, straggler_prob=0.5, max_delay=3,
                          drop_prob=0.2, leave_prob=0.3, join_prob=0.4)
    def trace(key):
        s = RoundScheduler(16, cfg, key=key)
        return [s.step() for _ in range(12)]

    a, b = trace(jax.random.PRNGKey(5)), trace(jax.random.PRNGKey(5))
    for ea, eb in zip(a, b):
        for fa, fb in zip(ea, eb):
            np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))
    c = trace(jax.random.PRNGKey(6))
    assert any(not np.array_equal(ea.participants, ec.participants)
               for ea, ec in zip(a, c))


def test_scheduler_shapes_and_roster_invariants():
    cfg = SchedulerConfig(participation=0.25, straggler_prob=1.0,
                          max_delay=2, leave_prob=0.5, join_prob=0.1)
    s = RoundScheduler(8, cfg, key=jax.random.PRNGKey(1))
    assert s.k == 2
    for _ in range(20):
        ev = s.step()
        assert ev.participants.shape == (2,)              # static jit shape
        assert s.active[ev.participants].all()            # drawn from roster
        assert s.active.sum() >= s.k                      # leaves are capped
        assert ((1 <= ev.delays) & (ev.delays <= 2)).all()  # all straggle


def test_scheduler_streams_are_knob_isolated():
    """Each per-round draw owns a PRNG substream: toggling the straggler
    / drop knobs cannot perturb the participant or churn draws (they
    used to share ONE per-round Generator, so any knob re-randomized
    everything after it)."""
    base = SchedulerConfig(participation=0.5, leave_prob=0.3, join_prob=0.4)
    noisy = SchedulerConfig(participation=0.5, leave_prob=0.3, join_prob=0.4,
                            straggler_prob=0.9, max_delay=3, drop_prob=0.5)
    a = RoundScheduler(16, base, key=jax.random.PRNGKey(3))
    b = RoundScheduler(16, noisy, key=jax.random.PRNGKey(3))
    for _ in range(12):
        ea, eb = a.step(), b.step()
        np.testing.assert_array_equal(ea.participants, eb.participants)
        np.testing.assert_array_equal(ea.joined, eb.joined)
        np.testing.assert_array_equal(ea.left, eb.left)


def test_scheduler_cohort_rng_does_not_advance_population_streams():
    """Cohort-level draws live on a reserved substream: consuming it
    between steps leaves the churn/participant/delay/drop streams
    bit-identical (a churn re-run is reproducible with or without the
    cohort engine in the loop)."""
    cfg = SchedulerConfig(participation=0.5, straggler_prob=0.5,
                          drop_prob=0.2, leave_prob=0.3, join_prob=0.4)
    a = RoundScheduler(16, cfg, key=jax.random.PRNGKey(4))
    b = RoundScheduler(16, cfg, key=jax.random.PRNGKey(4))
    for _ in range(10):
        b.cohort_rng().random(100)          # cohort draws on b only
        ea, eb = a.step(), b.step()
        for fa, fb in zip(ea, eb):
            np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))


def test_scheduler_diurnal_profile_quantized_participation():
    """A diurnal profile breathes the per-round participant count
    between trough and peak, always in whole cohort quanta, without
    touching the event streams' determinism."""
    from repro.server import DiurnalProfile
    prof = DiurnalProfile(period=8, trough=0.25, peak=1.0)
    s = RoundScheduler(64, SchedulerConfig(participation=0.5),
                       key=jax.random.PRNGKey(5), profile=prof, quantum=8)
    assert s.k == 32
    counts = [s.step().participants.size for _ in range(8)]
    assert all(c % 8 == 0 for c in counts)
    assert max(counts) == 32                      # peak round = full k
    assert min(counts) == 8                       # trough = 0.25 * 32
    assert len(set(counts)) > 1                   # it actually breathes
    # replay determinism holds with the profile on
    s2 = RoundScheduler(64, SchedulerConfig(participation=0.5),
                        key=jax.random.PRNGKey(5), profile=prof, quantum=8)
    counts2 = [s2.step().participants.size for _ in range(8)]
    assert counts == counts2


# -------------------------------------------------------------- multi-task

def test_multitask_single_task_parity_with_downstream(key):
    """One-task MultiTaskTrainer == core.downstream.sgd_train exactly."""
    rng = np.random.default_rng(0)
    y = jnp.asarray(rng.integers(0, 4, size=96), jnp.int32)
    feats = jax.nn.one_hot(y, 4) + 0.1 * jnp.asarray(
        rng.normal(size=(96, 4)), jnp.float32)

    trainer = MultiTaskTrainer(key, [TaskSpec("label", 4)], 4, lr=1e-3)
    trainer.fit(key, feats, {"label": y}, steps=25, batch=32)

    probe = DS.init_linear_probe(jax.random.fold_in(key, 0), 4, 4)
    ref = DS.sgd_train(key, DS.linear_probe, probe, feats, y,
                       steps=25, lr=1e-3, batch=32)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
        trainer.params["label"], ref)
    acc = trainer.accuracy(feats, {"label": y})["label"]
    assert acc == pytest.approx(DS.accuracy(DS.linear_probe, ref, feats, y),
                                abs=0.05)


def test_multitask_trains_all_heads_from_shared_features(key):
    rng = np.random.default_rng(1)
    y1 = jnp.asarray(rng.integers(0, 3, size=120), jnp.int32)
    y2 = jnp.asarray(rng.integers(0, 2, size=120), jnp.int32)
    feats = jnp.concatenate([jax.nn.one_hot(y1, 3), jax.nn.one_hot(y2, 2)],
                            axis=-1)
    trainer = MultiTaskTrainer(key, [TaskSpec("a", 3), TaskSpec("b", 2)], 5)
    trainer.fit(key, feats, {"a": y1, "b": y2}, steps=120, batch=64)
    acc = trainer.accuracy(feats, {"a": y1, "b": y2})
    assert acc["a"] > 0.9 and acc["b"] > 0.9
    with pytest.raises(ValueError, match="missing"):
        trainer.fit(key, feats, {"a": y1}, steps=1)


# ------------------------------------------------------------- tombstones

def test_retired_shims_raise_with_pointer_to_wire():
    """The long-deprecated PR-1 shims are GONE, not warning: importing
    any of them raises ImportError pointing at the unified wire layer."""
    with pytest.raises(ImportError, match="repro.server.CodeStore"):
        from repro.sim import IngestBuffer  # noqa: F401
    with pytest.raises(ImportError, match="repro.wire.CodePayload"):
        from repro.sim import PackedCodes  # noqa: F401
    with pytest.raises(ImportError, match="repro.wire.CodePayload"):
        from repro.sim.engine import PackedCodes  # noqa: F401
    import repro.sim
    assert "IngestBuffer" not in repro.sim.__all__
    assert "PackedCodes" not in repro.sim.__all__
