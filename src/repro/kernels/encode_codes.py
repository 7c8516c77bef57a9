"""Pallas TPU kernel: fused latent -> packed-code + EMA-stats encode.

The client uplink hot path (§2.2 Steps 3-5, §3.8 encode latency), the
mirror image of ``decode_codes.py``: where the server fuses packed words
-> features, the client must fuse latents -> packed words. The unfused
path materialized the (N, K) distance matrix in HBM (``vq.nearest_atom``),
wrote the int32 index tensor back to HBM, re-read it for ``pack_codes``,
and re-ran the encoder to rebuild the very same latents for the EMA
refresh. This kernel does the whole quantize-pack-stats tail in ONE pass:

  * **streaming argmin** — distances are computed per (positions, atoms)
    tile on the MXU with ``vq_nn.py``'s flash-style carry (running best
    distance + code in (BLOCK_N, 1) VMEM scratch columns), so the (N, K)
    matrix never exists;
  * **in-kernel packing** — on the last K step each N block's codes are
    OR-folded into the dense ``ceil(log2 K)``-bit uint32 word stream with
    ``pack_bits.py``'s constant-shift super-group layout: column ``j`` of
    every super-group is one sublane-strided read of the code scratch, so
    the int32 index tensor never touches HBM;
  * **on-chip EMA statistics** — the same codes drive a one-hot
    (positions, K) @ (positions, M) MXU matmul accumulating the per-atom
    counts and latent sums of Eq. 7-8, so the Step 5 refresh needs no
    second encoder pass (``ema.ema_update_from_stats`` consumes them).

Quantizer modes share one kernel:

  * plain VQ — score ``||e||^2 - 2 z.e^T`` per atom (row-constant
    ``||z||^2`` dropped), bit-identical to ``vq_nn.py``;
  * GSVQ — per-slice group match (Eq. 2): each grid step reads the full
    (positions, M) latents and, for every slice ``s``, matches the lane
    slice ``z[:, s*m:(s+1)*m]`` against slice ``s``'s own (K, m) atoms
    only (the codebook's columns ``[s*m, (s+1)*m)``), so a code is
    matched against K table rows, never the S*K rows of every slice.
    Per-atom sqrt distances are mean-pooled over each group's ``ng``
    atoms (a block-diagonal averaging matmul). Emitted codes are the
    within-slice group indices — exactly the transmitted alphabet — and
    EMA mass lands on each group's representative atom
    (``g * ng + ng//2``), matching ``octopus.client_codebook_refresh``.

The code scratch is flat in the transmitted order, position-major and
slice-minor (code ``p*S + s``): slice ``s``'s running best and code live
at rows ``s, s+S, s+2S, ...`` and are read and written there with
sublane-strided accesses, so the packing reads the scratch exactly as
in plain VQ (S = 1, one pass of the slice loop).

Records: the leading axis of ``z``/``codebooks`` is a record (client)
axis — every record is quantized against ITS OWN codebook and packed
into its own zero-padded word stream, so one dispatch encodes a whole
simulated population (per-record streams concatenate exactly like the
multi-record streams ``decode_codes`` already consumes, slice phase
restarting at 0 per record).

Every matmul runs at ``Precision.HIGHEST``: on the MXU a default-precision
f32 matmul rounds its operands to bf16, which would move near-tie argmins
and round the EMA latent sums.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pack_bits import packing_dims

BLOCK_N = 2048         # flat codes (positions x slices) per grid step
BLOCK_K = 512          # atoms of each slice's table per grid step

_HIGHEST = jax.lax.Precision.HIGHEST


def stacked_slice_table(codebooks, *, n_slices: int):
    """(R, K, M) codebooks -> (R, n_slices, K, m) per-slice tables.

    Table ``s`` of record ``r`` is the codebook's columns
    ``[s*m, (s+1)*m)``; group ``g`` of slice ``s`` is its ``ng``
    consecutive rows at ``g*ng``.
    """
    R, K, M = codebooks.shape
    m = M // n_slices
    return codebooks.reshape(R, K, n_slices, m).transpose(0, 2, 1, 3)


class EncodeTiling(NamedTuple):
    """How one ``encode_codes_pallas`` call is cut into grid steps."""
    grid: tuple            # (records, N blocks, K blocks)
    block_n: int           # flat codes per grid step
    block_p: int           # positions per grid step (block_n / n_slices)
    block_k: int           # atoms of each slice's table per grid step
    n_slices: int

    @property
    def rows_per_code(self) -> int:
        """Table rows each code is matched against, padding included."""
        return self.grid[2] * self.block_k


def encode_tiling(R: int, P: int, K: int, *, bits: int, n_groups: int = 1,
                  n_slices: int = 1, block_n: int = BLOCK_N,
                  block_k: int = BLOCK_K,
                  interpret: bool = False) -> EncodeTiling:
    """The grid and blocks for ``R`` records of ``P`` positions against
    ``K`` atoms: a pure function of the shapes, used by the kernel."""
    gsvq = n_groups > 1 or n_slices > 1
    S = n_slices if gsvq else 1
    ng = K // n_groups if gsvq else 1
    G, _ = packing_dims(bits)
    Pn = P * S                            # flat codes per record

    def n_block(unit):
        return max(unit, unit * (min(block_n, Pn + unit - 1) // unit))

    bn = n_block((G * S) // math.gcd(G, S))   # lcm: pack + slice alignment
    if not interpret and bn < Pn and ((bn // G) % 8 or (bn // S) % 8):
        # Mosaic: with several N blocks the word and latent blocks must
        # be whole (8, 128) tiles along their row axis
        bn = n_block(math.lcm(8 * G, 8 * S))
    bk = min(max(ng, ng * (block_k // ng)), K)
    return EncodeTiling(grid=(R, -(-Pn // bn), -(-K // bk)), block_n=bn,
                        block_p=bn // S, block_k=bk, n_slices=S)


def _encode_kernel(zf_ref, tab_ref, e2_ref, words_ref, counts_ref,
                   sums_ref, best_ref, code_ref, *, bits, G, W, n_slices,
                   n_groups, ng, n_atoms, count, block_k, vq_mode):
    """One (record, N block, K block) tile.

    zf_ref:  (1, BP, M)     latents of BP positions         [VMEM]
    tab_ref: (1, S, BK, m)  each slice's table tile         [VMEM]
    e2_ref:  (1, S, BK)     squared row norms of the tiles  [VMEM]
    words_ref:  (1, BN/G, W) packed words (last K step)
    counts_ref: (1, 1, K)    per-atom counts  (accumulated over N blocks)
    sums_ref:   (1, K, M)    per-atom sums    (accumulated over N blocks)
    best_ref/code_ref: (BN, 1) VMEM scratch carried across the K grid
    axis, BN = BP * S flat codes in transmitted order.
    """
    nstep = pl.program_id(1)
    kstep = pl.program_id(2)
    nk = pl.num_programs(2)
    BP = zf_ref.shape[1]
    m = tab_ref.shape[3]
    BN = BP * n_slices

    @pl.when(kstep == 0)
    def _init():
        best_ref[...] = jnp.full_like(best_ref, jnp.inf)
        code_ref[...] = jnp.zeros_like(code_ref)

    zf = zf_ref[0].astype(jnp.float32)                     # (BP, M)
    if not vq_mode:
        gb = block_k // ng                                 # groups per tile
        pool = (jax.lax.broadcasted_iota(jnp.int32, (block_k, gb), 0) // ng
                == jax.lax.broadcasted_iota(jnp.int32, (block_k, gb), 1)
                ).astype(jnp.float32)                      # (BK, gb) 0/1
    for s in range(n_slices):
        zs = zf[:, s * m:(s + 1) * m]                      # (BP, m)
        e = tab_ref[0, s].astype(jnp.float32)              # (BK, m)
        e2 = e2_ref[0, s:s + 1, :]                         # (1, BK)
        cross = jax.lax.dot_general(                       # MXU matmul
            zs, e, (((1,), (1,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=jnp.float32)            # (BP, BK)

        if vq_mode:
            # same score as vq_nn.py: ||e||^2 - 2 z.e^T, pad atoms masked
            d = e2 - 2.0 * cross
            gid = kstep * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            d = jnp.where(gid < n_atoms, d, jnp.inf)
            local_best = jnp.min(d, axis=-1, keepdims=True)    # (BP, 1)
            local_code = (jnp.argmin(d, axis=-1, keepdims=True)
                          .astype(jnp.int32) + kstep * block_k)
        else:
            # Eq. 2: sqrt per-atom distance, mean-pooled over each group
            z2 = jnp.sum(zs * zs, axis=-1, keepdims=True)  # (BP, 1)
            d2 = jnp.maximum(z2 - 2.0 * cross + e2, 0.0)
            d = jnp.sqrt(d2 + 1e-12)
            gd = jax.lax.dot_general(
                d, pool, (((1,), (0,)), ((), ())), precision=_HIGHEST,
                preferred_element_type=jnp.float32) / ng   # (BP, gb)
            g_gid = kstep * gb + jax.lax.broadcasted_iota(
                jnp.int32, (1, gb), 1)
            gd = jnp.where(g_gid < n_groups, gd, jnp.inf)  # pad groups
            local_best = jnp.min(gd, axis=-1, keepdims=True)
            # the WITHIN-SLICE group index: the transmitted alphabet
            local_code = (jnp.argmin(gd, axis=-1, keepdims=True)
                          .astype(jnp.int32) + kstep * gb)

        rows = pl.ds(s, BP, stride=n_slices)               # slice s's codes
        prev_best = best_ref[rows, :]
        take_new = local_best < prev_best                  # ties keep first
        best_ref[rows, :] = jnp.where(take_new, local_best, prev_best)
        code_ref[rows, :] = jnp.where(take_new, local_code,
                                      code_ref[rows, :])

    @pl.when(kstep == nk - 1)
    def _emit():
        base = nstep * BN

        def column(j, stride):
            """Codes at block rows j, j+stride, ... as a (BN/stride, 1)
            column; rows past ``count`` (stream padding) read as 0."""
            n = BN // stride
            c = code_ref[pl.ds(j, n, stride=stride), :]
            pos = base + j + stride * jax.lax.broadcasted_iota(
                jnp.int32, (n, 1), 0)
            valid = pos < count
            return jnp.where(valid, c, 0), valid

        # ---- pack: super-group row i holds codes i*G .. i*G+G-1, so word
        # column w ORs the constant-shifted strided code columns j
        cols = [None] * W
        for j in range(G):
            w0, s = divmod(j * bits, 32)
            c = column(j, G)[0].astype(jnp.uint32)         # (BN/G, 1)
            lo = c << s
            cols[w0] = lo if cols[w0] is None else cols[w0] | lo
            if s + bits > 32:                              # straddles a word
                hi = c >> (32 - s)
                cols[w0 + 1] = hi if cols[w0 + 1] is None else \
                    cols[w0 + 1] | hi
        for w in range(W):
            words_ref[0, :, w:w + 1] = cols[w]

        # ---- EMA statistics: one-hot MXU matmul onto representative
        # atoms; every slice votes its position's FULL latent (Eq. 7-8 via
        # client_codebook_refresh's broadcast), so the slices' one-hots
        # are folded onto their position row before the matmul
        kiota = jax.lax.broadcasted_iota(jnp.int32, (1, n_atoms), 1)
        onehot = None
        for s in range(n_slices):
            c, valid = column(s, n_slices)                 # (BP, 1)
            rep = c * ng + (ng // 2)                       # vq: ng == 1
            hit = ((rep == kiota) & valid).astype(jnp.float32)
            onehot = hit if onehot is None else onehot + hit
        cnt = jnp.sum(onehot, axis=0, keepdims=True)       # (1, K)
        sm = jax.lax.dot_general(                          # MXU scatter
            onehot, zf, (((0,), (0,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=jnp.float32)            # (K, M)

        @pl.when(nstep == 0)
        def _first():
            counts_ref[0] = cnt
            sums_ref[0] = sm

        @pl.when(nstep != 0)
        def _acc():
            counts_ref[0] += cnt
            sums_ref[0] += sm


@functools.partial(jax.jit, static_argnames=("bits", "n_groups", "n_slices",
                                             "block_n", "block_k",
                                             "interpret"))
def encode_codes_pallas(z, codebooks, *, bits: int, n_groups: int = 1,
                        n_slices: int = 1, block_n: int = BLOCK_N,
                        block_k: int = BLOCK_K, interpret: bool = False):
    """z: (R, P, M) latents + (R, K, M) per-record codebooks ->
    (words (R * ceil(P*S/G), W) uint32, counts (R, K), sums (R, K, M)).

    Record ``r``'s codes are packed into rows ``[r*nW, (r+1)*nW)`` of the
    word stream (each record zero-padded to whole super-groups, exactly
    like ``pack_codes`` on that record alone); counts/sums are its Eq. 7-8
    EMA sufficient statistics. ``n_groups``/``n_slices`` > 1 selects the
    GSVQ mode (codes are within-slice group indices).
    """
    R, P, M = z.shape
    Rc, K, M2 = codebooks.shape
    assert M == M2 and R == Rc, (z.shape, codebooks.shape)
    gsvq = n_groups > 1 or n_slices > 1
    if gsvq:
        assert M % n_slices == 0 and K % n_groups == 0, (M, K, n_groups,
                                                         n_slices)
    G, W = packing_dims(bits)
    t = encode_tiling(R, P, K, bits=bits, n_groups=n_groups,
                      n_slices=n_slices, block_n=block_n, block_k=block_k,
                      interpret=interpret)
    S, bn, BP, bk = t.n_slices, t.block_n, t.block_p, t.block_k
    _, NB, KB = t.grid
    m = M // S
    table = stacked_slice_table(codebooks, n_slices=S)     # (R, S, K, m)

    Pn = P * S                            # flat codes per record
    nW = -(-Pn // G)                      # payload rows per record
    zf = z
    pad_p = NB * BP - P
    if pad_p:
        zf = jnp.pad(zf, ((0, 0), (0, pad_p), (0, 0)))
    pad_k = KB * bk - K                   # pad atoms masked via their id
    if pad_k:
        table = jnp.pad(table, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    # row norms as a lane-major (R, S, atoms) input: reducing the tile
    # in-kernel would need a sublane -> lane relayout per grid step
    e2 = jnp.sum(table.astype(jnp.float32) ** 2, axis=-1)

    words, counts, sums = pl.pallas_call(
        functools.partial(_encode_kernel, bits=bits, G=G, W=W, n_slices=S,
                          n_groups=(n_groups if gsvq else K),
                          ng=(K // n_groups if gsvq else 1),
                          n_atoms=K, count=Pn, block_k=bk,
                          vq_mode=not gsvq),
        grid=t.grid,
        in_specs=[
            pl.BlockSpec((1, BP, M), lambda r, n, k: (r, n, 0)),
            pl.BlockSpec((1, S, bk, m), lambda r, n, k: (r, 0, k, 0)),
            pl.BlockSpec((1, S, bk), lambda r, n, k: (r, 0, k)),
        ],
        out_specs=[
            pl.BlockSpec((1, bn // G, W), lambda r, n, k: (r, n, 0)),
            pl.BlockSpec((1, 1, K), lambda r, n, k: (r, 0, 0)),
            pl.BlockSpec((1, K, M), lambda r, n, k: (r, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, NB * (bn // G), W), jnp.uint32),
            jax.ShapeDtypeStruct((R, 1, K), jnp.float32),
            jax.ShapeDtypeStruct((R, K, M), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bn, 1), jnp.float32),
            pltpu.VMEM((bn, 1), jnp.int32),
        ],
        interpret=interpret,
    )(zf, table, e2)
    return words[:, :nW].reshape(R * nW, W), counts[:, 0], sums
