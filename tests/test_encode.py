"""Fused client uplink pipeline (kernels/encode_codes.py).

The contracts that let the fused encode replace quantize-then-pack-then-
re-encode:
  * kernel parity — ops.encode_codes words == quantize -> pack_codes
    bit-exact for every packing width (VQ and grouped/sliced GSVQ),
    matching the jnp oracle and the use_ref fallback;
  * stats parity — the kernel's (counts, sums) drive ema_update_from_stats
    to the same EMAState as the classic ema_update to fp32 tolerance;
  * roundtrip — kernel-packed words decode through ops.decode_codes back
    to the features of the original indices;
  * protocol — client_round runs the encoder EXACTLY once after local
    fine-tuning (the seed path ran it three times), and the engine's
    fused population round is bit-identical to the per-client loop;
  * store — multi-record (per-client) kernel-packed payloads ingest and
    bulk-decode against the right version snapshots.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dvqae, ema, octopus as OC
from repro.core.dvqae import DVQAEConfig
from repro.core.gsvq import gsvq_quantize
from repro.core.vq import nearest_atom, quantize
from repro.kernels import ops, ref
from repro.kernels.pack_bits import code_bits, packing_dims


def _rand(key, shape):
    return jax.random.normal(key, shape)


# ------------------------------------------------------------------ kernel

@pytest.mark.parametrize("bits", [1, 3, 5, 8, 10, 12])
def test_encode_words_bitexact_vq(key, bits):
    """Fused words == nearest-atom -> pack_codes at every packing width."""
    K = 1 << bits
    M = 16
    for count in (1, 37, 300):
        k1, k2 = jax.random.split(jax.random.fold_in(key, bits * 1000 + count))
        z = _rand(k1, (1, count, M))
        cb = _rand(k2, (1, K, M))
        words, counts, sums = ops.encode_codes(z, cb, bits=bits,
                                               use_ref=False)   # Pallas
        idx = nearest_atom(z[0], cb[0])
        want = ops.pack_codes(idx, bits=bits)
        np.testing.assert_array_equal(np.asarray(words), np.asarray(want))
        for alt in (ref.encode_codes_ref(z, cb, bits=bits)[0],
                    ops.encode_codes(z, cb, bits=bits)[0],      # default
                    ops.encode_codes(z, cb, bits=bits, use_ref=True)[0]):
            np.testing.assert_array_equal(np.asarray(words), np.asarray(alt))


@pytest.mark.parametrize("n_groups,n_slices,K,M", [
    (8, 1, 64, 16), (4, 2, 64, 16), (8, 4, 64, 32), (1, 2, 64, 16),
    (16, 3, 64, 24), (4096, 2, 4096, 8)])
def test_encode_words_bitexact_gsvq(key, n_groups, n_slices, K, M):
    """GSVQ fused words == gsvq_quantize -> pack_codes (group alphabet),
    incl. the 12-bit group alphabet and a 3-slice phase pattern."""
    bits = code_bits(n_groups)
    count = 23 if K > 1024 else 201
    k1, k2 = jax.random.split(jax.random.fold_in(key, n_groups + n_slices))
    z = _rand(k1, (1, count, M))
    cb = _rand(k2, (1, K, M))
    words, counts, sums = ops.encode_codes(z, cb, bits=bits,
                                           n_groups=n_groups,
                                           n_slices=n_slices,
                                           use_ref=False)       # Pallas
    idx = gsvq_quantize(z[0], cb[0], n_groups=n_groups,
                        n_slices=n_slices).indices
    want = ops.pack_codes(idx, bits=bits)
    np.testing.assert_array_equal(np.asarray(words), np.asarray(want))
    rw, _, _ = ref.encode_codes_ref(z, cb, bits=bits, n_groups=n_groups,
                                    n_slices=n_slices)
    np.testing.assert_array_equal(np.asarray(words), np.asarray(rw))
    dw, _, _ = ops.encode_codes(z, cb, bits=bits, n_groups=n_groups,
                                n_slices=n_slices)              # default
    np.testing.assert_array_equal(np.asarray(words), np.asarray(dw))


def test_encode_multi_record_streams_pack_per_record(key):
    """Each record packs into ITS OWN zero-padded stream against ITS OWN
    codebook — identical to pack_codes on every record alone."""
    bits, K, M, P, R = 5, 32, 16, 45, 3          # P*1 not a multiple of G
    G, W = packing_dims(bits)
    ks = jax.random.split(key, 2 * R)
    z = jnp.stack([_rand(ks[i], (P, M)) for i in range(R)])
    cbs = jnp.stack([_rand(ks[R + i], (K, M)) for i in range(R)])
    for use_ref in (False, True):
        words, counts, sums = ops.encode_codes(z, cbs, bits=bits,
                                               use_ref=use_ref)
        nW = -(-P // G)
        assert words.shape == (R * nW, W)
        for r in range(R):
            idx = nearest_atom(z[r], cbs[r])
            np.testing.assert_array_equal(
                np.asarray(words[r * nW:(r + 1) * nW]),
                np.asarray(ops.pack_codes(idx, bits=bits)))


@pytest.mark.parametrize("n_groups,n_slices,K", [(1, 1, 32), (4, 2, 64)])
def test_encode_stats_match_ema_update(key, n_groups, n_slices, K):
    """Kernel (counts, sums) -> ema_update_from_stats == classic
    ema_update on the broadcast representative-atom assignment."""
    M = 16
    cfg = DVQAEConfig(latent_dim=M, codebook_size=K, n_groups=n_groups,
                      n_slices=n_slices)
    bits = OC.transmit_bits(cfg)
    k1, k2 = jax.random.split(key)
    z = _rand(k1, (1, 97, M))
    cb = _rand(k2, (1, K, M))
    _, counts, sums = ops.encode_codes(z, cb, bits=bits, n_groups=n_groups,
                                       n_slices=n_slices, use_ref=False)
    _, rcounts, rsums = ops.encode_codes(z, cb, bits=bits,
                                         n_groups=n_groups,
                                         n_slices=n_slices)     # default
    np.testing.assert_allclose(np.asarray(counts), np.asarray(rcounts),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(sums), np.asarray(rsums),
                               rtol=1e-5, atol=1e-5)
    state = ema.init_ema(cb[0])
    got = ema.ema_update_from_stats(state, counts[0], sums[0], gamma=0.7)
    if n_groups > 1 or n_slices > 1:
        idx = gsvq_quantize(z[0], cb[0], n_groups=n_groups,
                            n_slices=n_slices).indices
        ng = K // n_groups
        rep = idx * ng + ng // 2
        zv = jnp.broadcast_to(z[0][..., None, :], rep.shape + (M,))
    else:
        rep = nearest_atom(z[0], cb[0])
        zv = z[0]
    want = ema.ema_update(state, zv, rep, gamma=0.7)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bits", [3, 8, 12])
def test_encode_roundtrips_through_fused_decode(key, bits):
    """encode_codes words -> ops.decode_codes == codebook[indices]."""
    K, M, count = 1 << bits, 8, 130
    k1, k2 = jax.random.split(jax.random.fold_in(key, bits))
    z = _rand(k1, (1, count, M))
    cb = _rand(k2, (1, K, M))
    words, _, _ = ops.encode_codes(z, cb, bits=bits, use_ref=False)
    rows = ops.decode_codes(words, cb[0], bits=bits, count=count)
    idx = nearest_atom(z[0], cb[0])
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(cb[0][idx]))


def test_encode_kernel_block_sweep(key):
    """Words/stats invariant across block_n/block_k tilings."""
    bits, K, M, count = 6, 64, 16, 500
    k1, k2 = jax.random.split(key)
    z = _rand(k1, (1, count, M))
    cb = _rand(k2, (1, K, M))
    base = ops.encode_codes(z, cb, bits=bits, use_ref=False)
    for bn in (32, 96, 512):
        for bk in (16, 64, 512):
            got = ops.encode_codes(z, cb, bits=bits, block_n=bn, block_k=bk,
                                   use_ref=False)
            np.testing.assert_array_equal(np.asarray(got[0]),
                                          np.asarray(base[0]))
            np.testing.assert_allclose(np.asarray(got[1]),
                                       np.asarray(base[1]), rtol=1e-6)
            # sums reassociate across N-block accumulation order
            np.testing.assert_allclose(np.asarray(got[2]),
                                       np.asarray(base[2]), rtol=1e-5,
                                       atol=1e-5)


# (n_groups, n_slices, K, M): the benchmark cells' quantizer (3-bit codes),
# a 3-slice phase pattern (4-bit), and 4,096 single-atom groups (12-bit)
_CELL_Q = (8, 4, 256, 64)
_THREE_SLICE_Q = (16, 3, 64, 24)
_WIDE_Q = (4096, 2, 4096, 8)


@pytest.mark.parametrize("quant,block_n,block_k", [
    (_CELL_Q, 32, 32), (_CELL_Q, 128, 64), (_CELL_Q, 512, 512),
    (_CELL_Q, 4096, 64), (_THREE_SLICE_Q, 24, 4), (_THREE_SLICE_Q, 96, 16),
    (_THREE_SLICE_Q, 480, 128), (_THREE_SLICE_Q, 4096, 8),
    (_CELL_Q, 256, 96), (_THREE_SLICE_Q, 48, 12),   # last K tile padded
    (_WIDE_Q, 64, 512),         # each slice's 4,096 atoms span 8 K tiles
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_encode_kernel_block_sweep_gsvq(key, quant, block_n, block_k):
    """GSVQ words == the oracle's at every tiling, with block_k below and
    above each slice's K: each code is matched against its own slice's
    atoms, K tile by K tile, and lands at its flat (position, slice) row."""
    n_groups, n_slices, K, M = quant
    bits = code_bits(n_groups)
    P = 23 if K > 1024 else 75                   # P*S not a whole block
    k1, k2 = jax.random.split(jax.random.fold_in(key, block_n + block_k))
    z = _rand(k1, (2, P, M))
    cb = _rand(k2, (2, K, M))
    got = ops.encode_codes(z, cb, bits=bits, n_groups=n_groups,
                           n_slices=n_slices, block_n=block_n,
                           block_k=block_k, use_ref=False)
    want = ref.encode_codes_ref(z, cb, bits=bits, n_groups=n_groups,
                                n_slices=n_slices)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(want[2]),
                               rtol=1e-5, atol=1e-5)


def _pallas_grids(jaxpr):
    """The grid of every ``pallas_call`` in ``jaxpr``, nested ones too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield tuple(eqn.params["grid_mapping"].grid)
        for v in eqn.params.values():
            if hasattr(v, "jaxpr"):
                yield from _pallas_grids(v.jaxpr)


@pytest.mark.parametrize("R,P", [(16, 8192), (2, 128000)],
                         ids=["image", "speech"])
@pytest.mark.parametrize("mode", ["gsvq", "vq"])
def test_encode_tiling_matches_each_code_against_k_rows(R, P, mode):
    """At both cells' record shapes (K = 256 atoms of M = 64), a code is
    matched against K table rows in either mode; the slice-stacked GSVQ
    match gave S*K = 1,024. The kernel runs the tiling's grid."""
    from repro.kernels.encode_codes import encode_codes_pallas, encode_tiling
    K, M = 256, 64
    kw = (dict(bits=3, n_groups=8, n_slices=4) if mode == "gsvq"
          else dict(bits=8))
    t = encode_tiling(R, P, K, **kw)
    S = kw.get("n_slices", 1)
    assert t.rows_per_code == K
    assert t.block_p * S == t.block_n
    assert t.grid[:2] == (R, -(-P * S // t.block_n))
    jaxpr = jax.make_jaxpr(lambda z, cb: encode_codes_pallas(z, cb, **kw))(
        jax.ShapeDtypeStruct((R, P, M), jnp.float32),
        jax.ShapeDtypeStruct((R, K, M), jnp.float32))
    assert list(_pallas_grids(jaxpr.jaxpr)) == [t.grid]


# ---------------------------------------------------------------- protocol

def _count_encoder_passes(fn):
    """Run ``fn`` with repro.core.dvqae.encode wrapped by a counter."""
    calls = []
    real = dvqae.encode

    def counting(params, cfg, x):
        calls.append(1)
        return real(params, cfg, x)

    dvqae.encode = counting
    try:
        fn()
    finally:
        dvqae.encode = real
    return len(calls)


def test_client_round_single_encoder_pass(key):
    """Acceptance: after local fine-tuning, client_round runs the encoder
    exactly ONCE (the seed path ran forward, then forward + encode again
    inside the refresh — three passes for one batch of latents)."""
    cfg = DVQAEConfig(kind="image", in_channels=3, hidden=8, latent_dim=8,
                      codebook_size=16, n_res_blocks=1)
    srv = OC.server_init(key, cfg)
    cl = OC.client_init(srv)
    x = jax.random.normal(key, (2, 8, 8, 3))
    n = _count_encoder_passes(
        lambda: OC.client_round(cl, cfg, x, n_local_steps=0))
    assert n == 1, f"client_round ran the encoder {n}x"
    from repro.wire import round_words
    n = _count_encoder_passes(
        lambda: round_words(cl, cfg, x, n_local_steps=0))
    assert n == 1, f"round_words ran the encoder {n}x"
    # each fine-tune step legitimately adds exactly one gradient pass
    n = _count_encoder_passes(
        lambda: OC.client_round(cl, cfg, x, n_local_steps=2))
    assert n == 3


def test_codebook_refresh_single_pass_and_stats_shortcut(key):
    """client_codebook_refresh runs ONE encoder pass (was two network
    passes), and zero when handed precomputed stats."""
    cfg = DVQAEConfig(kind="image", in_channels=3, hidden=8, latent_dim=8,
                      codebook_size=16, n_res_blocks=1)
    srv = OC.server_init(key, cfg)
    cl = OC.client_init(srv)
    x = jax.random.normal(key, (2, 8, 8, 3))
    assert _count_encoder_passes(
        lambda: OC.client_codebook_refresh(cl, cfg, x)) == 1
    z, _ = OC.client_encode(cl.params, cfg, x)
    idx = OC.quantize_indices(cfg, z, cl.params["codebook"])
    stats = OC.refresh_stats(cfg, z, idx)
    assert _count_encoder_passes(
        lambda: OC.client_codebook_refresh(cl, cfg, None, stats=stats)) == 0
    got = OC.client_codebook_refresh(cl, cfg, None, stats=stats)
    want = OC.client_codebook_refresh(cl, cfg, x)
    np.testing.assert_allclose(np.asarray(got.params["codebook"]),
                               np.asarray(want.params["codebook"]),
                               rtol=1e-6)


@pytest.mark.parametrize("n_groups,n_slices", [(1, 1), (4, 2)])
def test_engine_fused_round_matches_client_loop(key, n_groups, n_slices):
    """The population round (vmapped encode + ONE fused dispatch) equals
    N single-client rounds: per-client packed records unpack to the loop
    indices bit-exactly and client states agree."""
    from repro.sim import SimEngine, stack_clients
    cfg = DVQAEConfig(kind="image", in_channels=3, hidden=8,
                      latent_dim=16 if n_slices > 1 else 8,
                      codebook_size=64 if n_groups > 1 else 16,
                      n_res_blocks=1, n_groups=n_groups, n_slices=n_slices)
    srv = OC.server_init(key, cfg)
    n_clients = 5
    data = jax.random.normal(key, (n_clients, 2, 8, 8, 3))
    engine = SimEngine(cfg, lr=1e-4, gamma=0.9)
    clients, packed = engine.round(engine.init_clients(srv, n_clients), data)
    assert packed.n_records == n_clients

    singles, idxs = [], []
    for i in range(n_clients):
        c, idx = OC.client_round(OC.client_init(srv), cfg, data[i],
                                 lr=1e-4, gamma=0.9)
        singles.append(c)
        idxs.append(idx)
    np.testing.assert_array_equal(np.asarray(packed.unpack()),
                                  np.asarray(jnp.stack(idxs)))
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-4, atol=3e-4),
        clients, stack_clients(singles))
    # the uplink decodes against the post-merge dictionary as before
    merged = engine.merge_into_server(srv, clients)
    feats = engine.dequantize(merged, packed)
    idx = packed.unpack()
    want = OC.codes_to_features(merged, cfg,
                                idx.reshape((-1,) + idx.shape[2:]))
    np.testing.assert_allclose(np.asarray(feats), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_transmission_nbytes_counts_per_client_padding(key):
    """The engine payload is one stream PER CLIENT — measured bytes cover
    every client's own super-group padding (what each radio sends), so
    nbytes == n_clients * per-client packed bytes."""
    from repro.sim import SimEngine
    cfg = DVQAEConfig(kind="image", in_channels=3, hidden=8, latent_dim=8,
                      codebook_size=16, n_res_blocks=1)
    srv = OC.server_init(key, cfg)
    engine = SimEngine(cfg, gamma=0.9)
    n_clients = 3
    data = jax.random.normal(key, (n_clients, 2, 8, 8, 3))
    _, packed = engine.round(engine.init_clients(srv, n_clients), data)
    one = ops.pack_codes(packed.unpack()[0], bits=packed.bits)
    assert packed.nbytes == n_clients * one.size * one.dtype.itemsize


# ------------------------------------------------------------------- store

def test_store_ingests_kernel_packed_population_rounds(key):
    """Multi-record engine payloads land in the CodeStore and bulk-decode
    (one dispatch per version) to the same features as their unpacked
    indices, across codebook versions."""
    from repro.server import CodebookRegistry, CodeStore
    from repro.sim import SimEngine
    cfg = DVQAEConfig(kind="image", in_channels=3, hidden=8, latent_dim=16,
                      codebook_size=64, n_res_blocks=1, n_groups=4,
                      n_slices=2)
    srv = OC.server_init(key, cfg)
    registry = CodebookRegistry(srv.params["codebook"])
    engine = SimEngine(cfg, gamma=0.9)
    clients = engine.init_clients(srv, 4)
    store = CodeStore(cfg)
    want = []
    for rnd in range(2):
        data = jax.random.normal(jax.random.fold_in(key, rnd), (4, 2, 8, 8, 3))
        clients, packed = engine.round(clients, data)
        store.add(packed, round=rnd, version=0)
        idx = packed.unpack()
        want.append(np.asarray(OC.codes_to_features(
            None, cfg, idx.reshape((-1,) + idx.shape[2:]),
            codebook=registry.get(0))))
    feats, _ = store.dataset(None, registry=registry)
    np.testing.assert_allclose(np.asarray(feats), np.concatenate(want),
                               rtol=1e-6, atol=1e-6)
