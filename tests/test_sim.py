"""Batched multi-client sim engine (repro.sim) + bit-packing codec.

The two contracts that let the engine replace the Python client loop:
  * one jitted vmap round over N stacked clients == N single-client
    ``octopus.client_round`` calls (allclose; indices exactly equal),
  * pack -> unpack of code indices is bit-exact, with Pallas/jnp parity.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import octopus as OC
from repro.core.dvqae import DVQAEConfig
from repro.kernels import ops, ref
from repro.kernels.pack_bits import code_bits, packing_dims
from repro.server import CodeStore
from repro.sim import SimEngine, replicate_clients, stack_clients

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def tiny_cfg():
    return DVQAEConfig(kind="image", in_channels=3, hidden=8, latent_dim=8,
                       codebook_size=16, n_res_blocks=1)


@pytest.fixture(scope="module")
def server(tiny_cfg):
    return OC.server_init(jax.random.PRNGKey(0), tiny_cfg)


def _assert_trees_close(a, b, **kw):
    jax.tree.map(lambda x, y: np.testing.assert_allclose(
        np.asarray(x), np.asarray(y), **kw), a, b)


# ------------------------------------------------------------------- codec

@pytest.mark.parametrize("n_atoms", [16, 256, 1024])
def test_pack_roundtrip_bitexact(n_atoms):
    bits = code_bits(n_atoms)
    rng = np.random.default_rng(n_atoms)
    for count in (1, 5, 257):
        codes = jnp.asarray(rng.integers(0, n_atoms, size=count), jnp.int32)
        packed_ref = ref.pack_codes_ref(codes, bits=bits)
        back = ref.unpack_codes_ref(packed_ref, bits=bits, count=count)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(codes))
        # Pallas kernels produce the identical word stream and codes
        packed = ops.pack_codes(codes, bits=bits)
        np.testing.assert_array_equal(np.asarray(packed),
                                      np.asarray(packed_ref))
        back2 = ops.unpack_codes(packed, bits=bits, count=count)
        np.testing.assert_array_equal(np.asarray(back2), np.asarray(codes))


@pytest.mark.parametrize("n_atoms", [16, 256, 1024])
def test_packed_size_is_dense(n_atoms):
    """ceil(log2 K) bits per code, plus at most one group of padding."""
    bits = code_bits(n_atoms)
    G, W = packing_dims(bits)
    codes = jnp.zeros((1000,), jnp.int32)
    packed = ops.pack_codes(codes, bits=bits)
    nbytes = packed.size * packed.dtype.itemsize
    assert nbytes >= (1000 * bits + 7) // 8
    assert nbytes <= ((1000 + G - 1) // G) * W * 4


def test_transmission_measures_packed_bytes(tiny_cfg, server, key):
    """A legacy Transmission carries the packed payload; nbytes is
    measured from it (CodePayload.nbytes is the single source) and the
    payload unpacks bit-exactly to the indices via the wire coercion
    (the unpack_transmission shim is a tombstone now)."""
    from repro.core.dvqae import forward
    from repro.wire import CodePayload, as_payload
    client = OC.client_init(server)
    x = jax.random.normal(key, (4, 8, 8, 3))
    idx = forward(client.params, tiny_cfg, x).latent.indices
    p = CodePayload.pack(idx, bits=OC.transmit_bits(tiny_cfg))
    tx = OC.Transmission(indices=idx, nbytes=p.nbytes,
                         labels=jnp.arange(4),
                         payload=p.payload, bits=p.bits)
    assert tx.payload is not None
    assert tx.bits == code_bits(tiny_cfg.codebook_size)
    assert tx.nbytes == tx.payload.size * tx.payload.dtype.itemsize
    back = as_payload(tx).unpack()
    np.testing.assert_array_equal(np.asarray(back), np.asarray(tx.indices))


# ------------------------------------------------------------------ engine

def test_engine_round_matches_client_loop(tiny_cfg, server, key):
    """N=64 clients in one jitted vmap == 64 single-client rounds."""
    n_clients = 64
    data = jax.random.normal(key, (n_clients, 2, 8, 8, 3))
    engine = SimEngine(tiny_cfg, lr=1e-4, gamma=0.9)
    clients = engine.init_clients(server, n_clients)
    clients, packed = engine.round(clients, data)

    singles, idxs = [], []
    for i in range(n_clients):
        c = OC.client_init(server)
        c, idx = OC.client_round(c, tiny_cfg, data[i], lr=1e-4, gamma=0.9)
        singles.append(c)
        idxs.append(idx)

    np.testing.assert_array_equal(np.asarray(packed.unpack()),
                                  np.asarray(jnp.stack(idxs)))
    # atol covers AdamW's lr-sized (1e-4) normalized first-step updates,
    # whose direction is reduction-order-sensitive where gradients ~ 0
    _assert_trees_close(clients, stack_clients(singles),
                        rtol=1e-4, atol=3e-4)


def test_engine_sharded_matches_unsharded(tiny_cfg, server, key):
    """shard_map over the mesh 'data' axis == plain vmap."""
    from repro.launch.mesh import make_host_mesh
    n_clients = 8
    data = jax.random.normal(key, (n_clients, 2, 8, 8, 3))
    plain = SimEngine(tiny_cfg, gamma=0.9)
    sharded = SimEngine(tiny_cfg, gamma=0.9, mesh=make_host_mesh())
    c1, p1 = plain.round(plain.init_clients(server, n_clients), data)
    c2, p2 = sharded.round(sharded.init_clients(server, n_clients), data)
    np.testing.assert_array_equal(np.asarray(p1.unpack()),
                                  np.asarray(p2.unpack()))
    _assert_trees_close(c1, c2, rtol=1e-4, atol=5e-5)


def test_engine_merge_matches_sequence_merge(tiny_cfg, server, key):
    n_clients = 4
    data = jax.random.normal(key, (n_clients, 2, 8, 8, 3))
    engine = SimEngine(tiny_cfg, gamma=0.9)
    clients, _ = engine.round(engine.init_clients(server, n_clients), data)
    merged = engine.merge_into_server(server, clients)
    ref_merged = OC.server_merge_codebooks(
        server, [clients.params["codebook"][i] for i in range(n_clients)],
        [clients.ema.counts[i] for i in range(n_clients)])
    np.testing.assert_allclose(np.asarray(merged.params["codebook"]),
                               np.asarray(ref_merged.params["codebook"]),
                               rtol=1e-6)


# ------------------------------------------------------------------ deploy

@pytest.mark.parametrize("n_clients", [2, 16, 17])
def test_init_clients_is_eager_replicate_bit_for_bit(tiny_cfg, server,
                                                     n_clients):
    """The one-dispatch deploy gives every leaf of the eager
    ``replicate_clients``: same values, dtypes and shapes."""
    engine = SimEngine(tiny_cfg)
    got = engine.init_clients(server, n_clients)
    want = replicate_clients(server, n_clients)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.shape[0] == n_clients
        assert g.dtype == w.dtype
        assert g.weak_type == w.weak_type
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_init_clients_compiles_once_per_size(tiny_cfg, server):
    """One program per ``n_clients``: a second deploy of the same size,
    from a server with new values, compiles nothing."""
    engine = SimEngine(tiny_cfg)
    engine.init_clients(server, 4)
    size = engine._deploy._cache_size()
    moved = server._replace(params={
        **server.params, "codebook": server.params["codebook"] + 1.0})
    again = engine.init_clients(moved, 4)
    assert engine._deploy._cache_size() == size
    np.testing.assert_array_equal(
        np.asarray(again.params["codebook"][3]),
        np.asarray(moved.params["codebook"]))
    engine.init_clients(server, 6)
    assert engine._deploy._cache_size() == size + 1


def test_cohort_round_deploys_once_per_cohort_inside_its_span(
        tiny_cfg, server, key, monkeypatch):
    """``CohortEngine.round`` still opens exactly one
    ``octopus/cohort/deploy`` per cohort, and the engine's deploy runs
    inside it."""
    from repro.obs import recorder
    from repro.sim import CohortEngine, CohortPlan
    log = []

    class Annotation:
        def __init__(self, name, **args):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name))

        def set_metadata(self, **args):
            pass

    monkeypatch.setattr(recorder, "TraceAnnotation", Annotation)
    eng = CohortEngine(tiny_cfg, gamma=0.9)
    deploy = eng.engine.init_clients

    def init_clients(*a, **kw):
        log.append(("call", "init_clients"))
        return deploy(*a, **kw)
    monkeypatch.setattr(eng.engine, "init_clients", init_clients)
    data = jax.random.normal(key, (7, 2, 8, 8, 3))
    plan = CohortPlan.build(np.arange(7), 3)       # cohorts of 3 and 4
    eng.round(server, plan, lambda ids: data[ids])
    deploys = [i for i, e in enumerate(log)
               if e == ("enter", "octopus/cohort/deploy")]
    assert len(deploys) == plan.n_cohorts == 2
    for i in deploys:
        assert log[i + 1] == ("call", "init_clients")
    assert log.count(("call", "init_clients")) == plan.n_cohorts
    assert log.count(("exit", "octopus/cohort/deploy")) == plan.n_cohorts


MESH_CHILD = textwrap.dedent("""
    import json, sys
    sys.path[:0] = [{src!r}]
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import octopus as OC
    from repro.core.dvqae import DVQAEConfig
    from repro.launch.mesh import make_host_mesh
    from repro.sim import SimEngine
    cfg = DVQAEConfig(kind="image", in_channels=3, hidden=8, latent_dim=8,
                      codebook_size=16, n_res_blocks=1)
    server = OC.server_init(jax.random.PRNGKey(0), cfg)
    data = jax.random.normal(jax.random.PRNGKey(1), (8, 2, 8, 8, 3))
    mesh = make_host_mesh()
    sharded = SimEngine(cfg, gamma=0.9, mesh=mesh)
    clients = sharded.init_clients(server, 8)
    want = NamedSharding(mesh, P("data"))
    leaves = jax.tree.leaves(clients)
    sharded_ok = all(
        getattr(x.sharding, "spec", None) == P("data")
        and x.sharding.is_equivalent_to(want, x.ndim)
        and len({{s.device for s in x.addressable_shards}}) == 4
        and all(s.data.shape[0] == 2 for s in x.addressable_shards)
        for x in leaves)
    plain = SimEngine(cfg, gamma=0.9)
    _, p1 = plain.round(plain.init_clients(server, 8), data)
    _, p2 = sharded.round(clients, data)
    print(json.dumps({{
        "devices": len(jax.devices()), "leaves": len(leaves),
        "sharded": sharded_ok,
        "codes_equal": bool(np.array_equal(np.asarray(p1.unpack()),
                                           np.asarray(p2.unpack())))}}))
""")


def test_init_clients_lands_sharded_on_a_mesh():
    """On four (virtual CPU) devices, in a child process that sets the
    device count before JAX starts: every deployed leaf comes out
    sharded ``P("data")``, two clients per device, and the sharded round
    on them sends the plain engine's codes."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = MESH_CHILD.format(src=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4 and out["leaves"] > 0
    assert out["sharded"] is True
    assert out["codes_equal"] is True


# ------------------------------------------------------------------ ingest

def test_code_store_accumulates_engine_rounds(tiny_cfg, server, key):
    """Engine uplinks land in repro.server.CodeStore (the IngestBuffer
    successor): measured byte totals, lazily-decoded dataset, labels."""
    n_clients, b = 4, 2
    data = jax.random.normal(key, (n_clients, b, 8, 8, 3))
    engine = SimEngine(tiny_cfg, gamma=0.9)
    clients = engine.init_clients(server, n_clients)
    store = CodeStore(tiny_cfg)
    packeds = []
    for r in range(3):
        clients, packed = engine.round(clients, data)
        store.add(packed, labels=jnp.full((n_clients, b), r % 2, jnp.int32))
        packeds.append(packed)
    assert len(store) == 3
    assert store.total_bytes == sum(p.nbytes for p in packeds)
    assert store.ingested_bytes == store.total_bytes   # nothing evicted
    assert store.n_samples == 3 * n_clients * b
    codes = store.codes()
    assert codes.shape[0] == store.n_samples
    assert codes.dtype == jnp.int32
    feats, labels = store.dataset(server)
    assert feats.shape[0] == labels["label"].shape[0] == store.n_samples
    np.testing.assert_array_equal(
        np.asarray(labels["label"]),
        np.repeat([0, 1, 0], n_clients * b))


# -------------------------------------------------------------------- data

def test_stacked_batches_shapes_and_pool(key):
    """stacked_batches yields (C, B, ...) rounds drawn without
    replacement from each client's own shard."""
    from repro.data import make_images, partition_stacked, stacked_batches
    data = make_images(key, 48, size=8, n_identities=4)
    stacked = partition_stacked(data, 4, regime="iid")
    n_per = stacked.x.shape[1]
    seen = [[] for _ in range(4)]
    got = 0
    for b in stacked_batches(stacked, 4, epochs=2):
        assert b.x.shape == (4, 4, 8, 8, 3)
        assert b.content.shape == (4, 4)
        got += 1
        for c in range(4):
            seen[c].extend(np.asarray(b.content[c]).tolist())
    assert got == 2 * (n_per // 4)
    for c in range(4):
        own = np.asarray(stacked.content[c])
        # each epoch is a permutation of the client's shard labels
        assert sorted(seen[c][:n_per]) == sorted(own.tolist())


# ------------------------------------------------------------------ fedavg

def test_fedavg_batched_matches_sequential(key):
    from repro.core.downstream import conv_classifier, init_conv_classifier
    from repro.core.fedavg import (FedConfig, fedavg_train,
                                   fedavg_train_batched)
    from repro.data import make_images, partition_stacked

    data = make_images(key, 64, size=8, n_identities=4)
    stacked = partition_stacked(data, 4, regime="iid")
    shards = [type(data)(x=stacked.x[i], content=stacked.content[i],
                         style=stacked.style[i]) for i in range(4)]
    clf = init_conv_classifier(key, in_channels=3, n_classes=4)
    fc = FedConfig(rounds=2, local_epochs=1, local_batch=8,
                   dp_clip=0.5, dp_noise=0.01)
    p_seq = fedavg_train(key, conv_classifier, clf, shards,
                         lambda d: d.content, fc)
    p_bat = fedavg_train_batched(key, conv_classifier, clf, stacked.x,
                                 stacked.content, fc)
    _assert_trees_close(p_seq, p_bat, rtol=1e-4, atol=1e-5)
