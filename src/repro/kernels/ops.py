"""Jit'd public wrappers for the Pallas kernels.

The kernel path is decided from the platform when a wrapper is CALLED
(:func:`interpret_mode`), never when this module is imported: on TPU
every kernel compiles through Mosaic, on CPU it runs in Pallas
interpret mode (the encode wrapper takes the bit-identical jnp oracle
there, see :func:`encode_codes`), and any other platform is an error —
there is no silent fallback that could hide the device.

Every dispatch runs under a ``jax.named_scope("octopus/<op>")`` so
device traces (``jax.profiler``) attribute kernel time to the protocol
step that dispatched it. Scopes only label the jaxpr/HLO — numerics,
dispatch counts and compiled programs are bit-identical with or without
them (the flight-recorder neutrality suite pins this).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .decode_codes import decode_codes_pallas
from .encode_codes import encode_codes_pallas
from .flash_attention import flash_attention_pallas
from .pack_bits import code_bits, pack_codes_pallas, unpack_codes_pallas
from .rmsnorm import rmsnorm_pallas
from .selective_scan import selective_scan_pallas
from .vq_nn import vq_nearest_pallas


def interpret_mode() -> bool:
    """False on TPU (compiled kernels), True on CPU (interpret mode);
    raises on any other platform."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"no kernel path for platform {backend!r}: the Pallas kernels "
        f"compile for TPU and run in interpret mode on CPU only")


def vq_nearest(z, codebook, **kw):
    """(N, M), (K, M) -> (N,) int32 nearest codebook atom per row."""
    kw.setdefault("interpret", interpret_mode())
    if kw["interpret"]:
        # off-TPU there is no VMEM budget: fatter N blocks mean fewer
        # (traced) grid steps, which dominates interpret-mode runtime
        kw.setdefault("block_n", 4096)
    with jax.named_scope("octopus/vq_nearest"):
        return vq_nearest_pallas(z, codebook, **kw)


def pack_codes(codes, *, bits, **kw):
    """Flat/any-shape int codes -> (n_groups, W) uint32 dense bit-stream
    at ceil(log2 K) bits per code (see kernels/pack_bits.py layout)."""
    kw.setdefault("interpret", interpret_mode())
    with jax.named_scope("octopus/pack_codes"):
        return pack_codes_pallas(codes, bits=bits, **kw)


def unpack_codes(words, *, bits, count, **kw):
    """(n_groups, W) uint32 words -> (count,) int32 codes, bit-exact."""
    kw.setdefault("interpret", interpret_mode())
    with jax.named_scope("octopus/unpack_codes"):
        return unpack_codes_pallas(words, bits=bits, count=count, **kw)


def decode_codes(words, table, *, bits=None, count=None, n_slices=1,
                 phases=None, use_ref=False, **kw):
    """Fused packed-word -> feature decode: (n, W) uint32 words + a
    (n_slices*R, F) decode table -> (count, F) rows, without the int32
    index or gathered-atom tensors ever hitting HBM (see
    kernels/decode_codes.py for the layout and the GSVQ mean-table
    contract). ``use_ref=True`` falls back to the pure-jnp oracle
    (ref.decode_codes_ref) — same result, no Pallas dispatch.

    ``words`` may be a ``repro.wire.CodePayload`` directly — bits/count
    (and per-record slice phases) then come from the carrier, and the
    result is the payload's (count, F) real rows in stream order."""
    if hasattr(words, "unpack"):               # wire carrier
        if bits is not None or count is not None or phases is not None:
            raise TypeError(
                "decode_codes got a CodePayload AND explicit bits=/count=/"
                "phases= — the carrier's own fields are authoritative; "
                "drop the arguments (or pass the raw word stream)")
        from repro.wire.codec import decode_rows
        return decode_rows(words, table, n_slices=n_slices,
                           use_ref=use_ref, **kw)
    if bits is None or count is None:
        raise TypeError("decode_codes needs bits= and count= for a raw "
                        "word stream (or pass a CodePayload)")
    if use_ref:
        from .ref import decode_codes_ref
        with jax.named_scope("octopus/decode_codes_ref"):
            return decode_codes_ref(words, table, bits=bits, count=count,
                                    n_slices=n_slices, phases=phases)
    kw.setdefault("interpret", interpret_mode())
    with jax.named_scope("octopus/decode_codes"):
        return decode_codes_pallas(words, table, bits=bits, count=count,
                                   n_slices=n_slices, phases=phases, **kw)


def encode_codes(z, codebooks, *, bits, n_groups=1, n_slices=1,
                 use_ref=None, **kw):
    """Fused latent -> packed-code encode with on-chip EMA statistics:
    (R, P, M) latents + (R, K, M) per-record codebooks -> (words
    (R*nW, W) uint32, counts (R, K), sums (R, K, M)) in ONE pass — the
    (N, K) distance matrix and the int32 index tensor never hit HBM (see
    kernels/encode_codes.py for modes and the record/packing layout).

    ``use_ref``: None (default) runs the compiled Pallas kernel on TPU
    and the pure-jnp oracle (ref.encode_codes_ref) on CPU — the oracle
    emits bit-identical words, and unlike the other wrappers' interpret
    mode it keeps CPU CI fast (the XLA-fused oracle beats the
    interpreted grid). True/False force the oracle/kernel; on CPU the
    forced kernel runs with interpret=True."""
    interpret = interpret_mode()
    if use_ref or (use_ref is None and interpret):
        from .ref import encode_codes_ref
        with jax.named_scope("octopus/encode_codes_ref"):
            return encode_codes_ref(z, codebooks, bits=bits,
                                    n_groups=n_groups, n_slices=n_slices)
    kw.setdefault("interpret", interpret)
    if kw["interpret"]:
        # off-TPU there is no VMEM budget: fatter N blocks mean fewer
        # (traced) grid steps, which dominates interpret-mode runtime
        kw.setdefault("block_n", 4096)
    with jax.named_scope("octopus/encode_codes"):
        return encode_codes_pallas(z, codebooks, bits=bits,
                                   n_groups=n_groups, n_slices=n_slices,
                                   **kw)


def encode_payload(z, codebooks, *, bits, shape, n_groups=1, n_slices=1,
                   version=0, labels=None, n_samples=None, **kw):
    """``encode_codes`` speaking the wire natively: same fused dispatch,
    but the words come back wrapped as a ``repro.wire.CodePayload`` —
    one per-record stream per codebook record (``n_records ==
    z.shape[0]``), stamped with ``version``/``labels``/``privatized``.
    ``shape`` is the transmitted index shape (R, P[, n_c]). Returns
    (payload, counts, sums)."""
    from repro.wire.payload import CodePayload
    words, counts, sums = encode_codes(z, codebooks, bits=bits,
                                       n_groups=n_groups,
                                       n_slices=n_slices, **kw)
    payload = CodePayload.from_words(
        words, bits=bits, shape=shape, n_records=int(z.shape[0]),
        version=version, labels=labels, n_samples=n_samples,
        privatized=True)
    return payload, counts, sums


def flash_attention(q, k, v, *, causal=True, window=0, **kw):
    """(B,T,Hq,D) with GQA k/v (B,T,Hkv,D): repeat kv then run the kernel."""
    kw.setdefault("interpret", interpret_mode())
    q_per_kv = q.shape[2] // k.shape[2]
    if q_per_kv > 1:
        k = jnp.repeat(k, q_per_kv, axis=2)
        v = jnp.repeat(v, q_per_kv, axis=2)
    return flash_attention_pallas(q, k, v, causal=causal, window=window, **kw)


def rmsnorm(x, scale, *, eps=1e-6, **kw):
    kw.setdefault("interpret", interpret_mode())
    return rmsnorm_pallas(x, scale, eps=eps, **kw)


def selective_scan(decay, inp, c, h0, **kw):
    """Fused Mamba recurrence + output contraction (see selective_scan.py)."""
    kw.setdefault("interpret", interpret_mode())
    return selective_scan_pallas(decay, inp, c, h0, **kw)
