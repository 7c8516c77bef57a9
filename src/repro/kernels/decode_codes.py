"""Pallas TPU kernel: fused packed-code -> feature decode (Step 6 hot path).

The server's Step 6 front door used to decode uplinks in three
materialized hops: packed uint32 words -> int32 indices (HBM) -> gathered
atom rows (HBM, (N, N_g, m) for GSVQ) -> feature rows. This kernel goes
straight from the dense bit-stream to feature rows in ONE pass: per
(BLOCK_G, W) tile it unpacks the ``b``-bit codes with the same
constant-shift super-group layout as ``pack_bits.py`` and immediately
gathers the decode-table row on-chip via a one-hot MXU matmul, so the
intermediate index and atom tensors never touch HBM.

The decode table unifies both quantizer paths:

  * plain VQ  — the codebook itself, ``(K, M)``; a code gathers its atom.
  * GSVQ      — the precomputed per-slice group-mean table
    ``(n_slices * n_groups, m)`` (``gsvq_group_mean_table``): gathering
    row ``s * n_groups + g`` is mathematically identical to
    ``gsvq_dequantize_indices``'s uniform group average, but costs one
    row instead of an ``(N, N_g, m)`` gather + mean.

Slice bookkeeping: a flat GSVQ code stream interleaves slices — code
``j`` of a record belongs to slice ``j % n_slices``. Because streams are
padded to whole super-groups (and several records may be concatenated
into one dispatch), the kernel takes a per-group ``phase`` vector: the
slice id of the group's first code. Within a group, column ``j`` is
slice ``(phase + j) % n_slices`` — a per-row add + mod, no cross-lane
work. One-hot gather keeps everything on the MXU (the same trick the
roofline favours over dynamic row gathers on TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pack_bits import packing_dims

BLOCK_G = 256          # stream super-groups per grid step


def stream_phases(n_stream_groups: int, bits: int, n_slices: int):
    """Slice id of each super-group's first code for a contiguous record.

    Group ``g`` starts at flat code offset ``g * G``, so its phase is
    ``(g * G) % n_slices``. Concatenated multi-record streams build their
    phase vector per record (each record's slice phase restarts at 0).
    """
    G, _ = packing_dims(bits)
    return (jnp.arange(n_stream_groups, dtype=jnp.int32) * G) % n_slices


def _decode_kernel(words_ref, phase_ref, table_ref, out_ref, *, bits, G, W,
                   n_slices, rows):
    """One (BG, W) word tile -> (BG, G, F) feature tile.

    Unrolls the G-column loop with constant shifts (same layout as
    ``_unpack_kernel``); each column's codes gather their table row via a
    one-hot (BG, rows*n_slices) @ (rows*n_slices, F) MXU matmul.
    """
    words = words_ref[...]                                 # (BG, W) uint32
    table = table_ref[...].astype(jnp.float32)             # (S*rows, F)
    mask = jnp.uint32((1 << bits) - 1)
    n_tab = table.shape[0]
    tab_iota = jax.lax.broadcasted_iota(jnp.int32, (1, n_tab), 1)
    for j in range(G):
        o = j * bits
        w0, s = divmod(o, 32)
        v = words[:, w0:w0 + 1] >> s
        if s + bits > 32:                                  # straddles a word
            v = v | (words[:, w0 + 1:w0 + 2] << (32 - s))
        code = (v & mask).astype(jnp.int32)                # (BG, 1)
        if n_slices > 1:
            sl = jax.lax.rem(phase_ref[...] + j, n_slices)
            code = sl * rows + code                        # row in stacked table
        onehot = (code == tab_iota).astype(jnp.float32)    # (BG, n_tab)
        # HIGHEST: a default-precision MXU pass would round the f32 table
        # to bf16; the multi-pass product of an exact 0/1 one-hot
        # reassembles every table entry bit-exactly
        feat = jax.lax.dot_general(                        # MXU gather
            onehot, table, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        out_ref[:, j, :] = feat.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "count", "n_slices",
                                             "block_g", "interpret"))
def decode_codes_pallas(words, table, *, bits: int, count: int,
                        n_slices: int = 1, phases=None,
                        block_g: int = BLOCK_G, interpret: bool = False):
    """(n_groups, W) uint32 words + (n_slices*R, F) table -> (count, F).

    Row ``i`` is the decode-table row of packed code ``i`` (pad codes
    beyond ``count`` are dropped). ``phases``: per-group slice id of the
    group's first code (default: a single contiguous record starting at
    slice 0 — see :func:`stream_phases`).
    """
    G, W = packing_dims(bits)
    n = words.shape[0]
    n_tab, F = table.shape
    assert n_tab % n_slices == 0, (n_tab, n_slices)
    rows = n_tab // n_slices
    if phases is None:
        phases = stream_phases(n, bits, n_slices)
    phases = jnp.asarray(phases, jnp.int32).reshape(-1, 1)
    block_g = min(block_g, max(8, n))
    pad = (-n) % block_g
    if pad:
        words = jnp.pad(words, ((0, pad), (0, 0)))
        phases = jnp.pad(phases, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_decode_kernel, bits=bits, G=G, W=W,
                          n_slices=n_slices, rows=rows),
        grid=((n + pad) // block_g,),
        in_specs=[
            pl.BlockSpec((block_g, W), lambda g: (g, 0)),
            pl.BlockSpec((block_g, 1), lambda g: (g, 0)),
            pl.BlockSpec((n_tab, F), lambda g: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_g, G, F), lambda g: (g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n + pad, G, F), table.dtype),
        interpret=interpret,
    )(words, phases, table)
    return out.reshape(-1, F)[:count]
