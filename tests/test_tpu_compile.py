"""AOT compiles of the main-path kernels for a described TPU v5e.

The TPU compiler is installed even where no chip is attached: a
topology that is described, not present, still compiles, and refuses
what the chip would refuse — blocks off the (8, 128) tiling, more
scoped VMEM than a kernel may take, layouts Mosaic cannot take. Interpret
mode never sees those. Every test compiles at the repo's full DVQ-AE
width (K=256 atoms of M=64, 64x64 images -> 256 positions, 32 images
per client, 16 records per dispatch) and asserts that the compiled HLO
holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and test workers import
every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.dvqae import DVQAEConfig
from repro.kernels.pack_bits import code_bits, packing_dims

R, P, M, K = 16, 32 * 256, 64, 256          # records, codes/record, widths
GSVQ = (8, 4)                                # (n_groups, n_slices)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # pragma: no cover
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described device can write to the persistent cache but never
    # read it back; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("mode", ["vq", "gsvq"])
def test_encode_codes_compiles(one_chip, mode):
    from repro.kernels.encode_codes import encode_codes_pallas
    n_groups, n_slices = GSVQ if mode == "gsvq" else (1, 1)
    bits = code_bits(n_groups if mode == "gsvq" else K)
    _assert_kernel(
        lambda z, cb: encode_codes_pallas(z, cb, bits=bits,
                                          n_groups=n_groups,
                                          n_slices=n_slices),
        _spec(one_chip, (R, P, M)), _spec(one_chip, (R, K, M)))


def test_encode_codes_compiles_speech_records(one_chip):
    """GSVQ encode at the speech cell's shape: 2 records of 128,000
    positions, 512,000 codes each."""
    from repro.kernels.encode_codes import encode_codes_pallas
    n_groups, n_slices = GSVQ
    _assert_kernel(
        lambda z, cb: encode_codes_pallas(z, cb, bits=code_bits(n_groups),
                                          n_groups=n_groups,
                                          n_slices=n_slices),
        _spec(one_chip, (2, 128_000, M)), _spec(one_chip, (2, K, M)))


def test_vq_nearest_compiles(one_chip):
    from repro.kernels.vq_nn import vq_nearest_pallas
    _assert_kernel(vq_nearest_pallas, _spec(one_chip, (R * P, M)),
                   _spec(one_chip, (K, M)))


@pytest.mark.parametrize("mode", ["vq", "gsvq"])
def test_decode_codes_compiles(one_chip, mode):
    from repro.kernels.decode_codes import decode_codes_pallas
    n_groups, n_slices = GSVQ if mode == "gsvq" else (1, 1)
    bits = code_bits(n_groups if mode == "gsvq" else K)
    rows = n_slices * (n_groups if mode == "gsvq" else K)
    count = R * P * n_slices
    G, W = packing_dims(bits)
    _assert_kernel(
        lambda w, t: decode_codes_pallas(w, t, bits=bits, count=count,
                                         n_slices=n_slices),
        _spec(one_chip, (-(-count // G), W), jnp.uint32),
        _spec(one_chip, (rows, M // n_slices)))


@pytest.mark.parametrize("bits", [5, 8, 10])
def test_pack_unpack_compile(one_chip, bits):
    from repro.kernels.pack_bits import pack_codes_pallas, unpack_codes_pallas
    G, W = packing_dims(bits)
    count = R * P
    _assert_kernel(lambda c: pack_codes_pallas(c, bits=bits),
                   _spec(one_chip, (count,), jnp.int32))
    _assert_kernel(lambda w: unpack_codes_pallas(w, bits=bits, count=count),
                   _spec(one_chip, (-(-count // G), W), jnp.uint32))


def test_client_round_compiles(one_chip, monkeypatch):
    """One whole jitted client round (fine-tune step, encoder pass, fused
    encode, EMA refresh) with ``ops`` steered to the compiled kernels —
    on this CPU host the platform rule would pick interpret mode."""
    from repro.core import octopus as OC
    from repro.kernels import ops
    from repro.wire import round_words
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    cfg = DVQAEConfig()
    state = jax.eval_shape(
        lambda: OC.client_init(OC.server_init(jax.random.PRNGKey(0), cfg)))
    state = jax.tree.map(lambda s: _spec(one_chip, s.shape, s.dtype), state)
    batch = _spec(one_chip, (P // 256, 64, 64, cfg.in_channels))
    compiled = _assert_kernel(lambda c, x: round_words(c, cfg, x),
                              state, batch)
    text = compiled.as_text()
    # the encoder's quantizer (vq_nn) and the fused uplink (encode_codes)
    assert text.count("tpu_custom_call") >= 2


def test_gsvq_finetune_quantizer_compiles_lean(one_chip):
    """The fine-tune's GSVQ quantizer at an image cohort (16 clients of
    8,192 positions, each with its own (256, 64) codebook): the vmapped
    gradient of its Eq. 6 terms (codebook, commitment and the
    straight-through output) w.r.t. z. Eq. 3 comes from the Eq. 2 (N, K)
    matrix by matmuls; a gathered (N, N_g, m) atom tensor would need
    about 5.2e9 B of temporaries and 4.3e10 B of traffic here."""
    from repro.core.gsvq import gsvq_quantize
    n_groups, n_slices = GSVQ

    def terms(z, cb):
        q = gsvq_quantize(z, cb, n_groups=n_groups, n_slices=n_slices)
        return (q.codebook_loss + 0.25 * q.commit_loss
                + jnp.mean(jnp.square(q.quantized)))

    compiled = jax.jit(jax.vmap(jax.grad(terms))).lower(
        _spec(one_chip, (R, P, M)), _spec(one_chip, (R, K, M))).compile()
    assert compiled.memory_analysis().temp_size_in_bytes <= 2.0e9
    assert compiled.cost_analysis()["bytes accessed"] <= 1.5e10
