"""Pure-jnp oracles for every Pallas kernel. Tests assert_allclose against
these across shape/dtype sweeps."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def vq_nearest_ref(z, codebook):
    """(N, M), (K, M) -> (N,) int32. Brute-force pairwise L2 argmin."""
    z = z.astype(jnp.float32)
    e = codebook.astype(jnp.float32)
    d = (jnp.sum(z * z, -1, keepdims=True)
         - 2.0 * z @ e.T
         + jnp.sum(e * e, -1)[None, :])
    return jnp.argmin(d, axis=-1).astype(jnp.int32)


def flash_attention_ref(q, k, v, *, causal=True, window=0, scale=None):
    """(B, T, H, D) x3 -> (B, T, H, D). Materialised softmax attention."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.array(D, jnp.float32))
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    qpos = jnp.arange(Tq)[:, None]
    kpos = jnp.arange(Tk)[None, :]
    mask = jnp.ones((Tq, Tk), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def rmsnorm_ref(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)


def pack_codes_ref(codes, *, bits: int):
    """Flat int codes -> (n_groups, W) uint32 dense bit-stream.

    Same super-group layout as kernels/pack_bits.py: code j of a group
    occupies bits [j*b, (j+1)*b) of the group's lcm(b, 32)-bit payload.
    """
    from .pack_bits import _group_codes, packing_dims
    G, W = packing_dims(bits)
    grp = _group_codes(codes, bits)                       # (n_groups, G)
    cols = [jnp.zeros_like(grp[:, 0]) for _ in range(W)]
    for j in range(G):
        w0, s = divmod(j * bits, 32)
        c = grp[:, j]
        cols[w0] = cols[w0] | (c << s)
        if s + bits > 32:
            cols[w0 + 1] = cols[w0 + 1] | (c >> (32 - s))
    return jnp.stack(cols, axis=1)


def unpack_codes_ref(words, *, bits: int, count: int):
    """(n_groups, W) uint32 -> (count,) int32 codes."""
    from .pack_bits import packing_dims
    G, W = packing_dims(bits)
    mask = jnp.uint32((1 << bits) - 1)
    cols = []
    for j in range(G):
        w0, s = divmod(j * bits, 32)
        v = words[:, w0] >> s
        if s + bits > 32:
            v = v | (words[:, w0 + 1] << (32 - s))
        cols.append(v & mask)
    return jnp.stack(cols, axis=1).reshape(-1)[:count].astype(jnp.int32)


def decode_codes_ref(words, table, *, bits: int, count: int,
                     n_slices: int = 1, phases=None):
    """(n_groups, W) uint32 + (n_slices*R, F) table -> (count, F) rows.

    Unpack-then-gather oracle for kernels/decode_codes.py: code ``j`` of
    stream group ``g`` belongs to slice ``(phases[g] + j) % n_slices``
    and gathers table row ``slice * R + code``.
    """
    from .pack_bits import packing_dims
    G, _ = packing_dims(bits)
    n = words.shape[0]
    codes = unpack_codes_ref(words, bits=bits, count=n * G)
    if n_slices > 1:
        pos = jnp.arange(n * G, dtype=jnp.int32)
        if phases is None:
            sl = pos % n_slices
        else:
            ph = jnp.asarray(phases, jnp.int32).reshape(-1)
            sl = (ph[pos // G] + pos % G) % n_slices
        codes = sl * (table.shape[0] // n_slices) + codes
    return table[codes[:count]]


def encode_scores_ref(z, codebooks, *, n_groups: int = 1, n_slices: int = 1):
    """The scores the encode argmin runs over, lowest wins.

    Plain VQ: ``||e||^2 - 2 z.e^T`` per atom, (R, P, K). GSVQ: the Eq. 2
    per-slice group match — sqrt per-atom distances mean-pooled over
    each group — as (R, P, n_slices, n_groups).
    """
    R, P, M = z.shape
    K = codebooks.shape[1]
    zf = z.astype(jnp.float32)
    cb = codebooks.astype(jnp.float32)
    if n_groups > 1 or n_slices > 1:
        m = M // n_slices
        ng = K // n_groups
        zsl = zf.reshape(R, P, n_slices, m)
        csl = cb.reshape(R, K, n_slices, m).transpose(0, 2, 1, 3)

        def per_slice(z_s, cb_s):                       # (P, m), (K, m)
            z2 = jnp.sum(z_s * z_s, -1, keepdims=True)
            e2 = jnp.sum(cb_s * cb_s, -1)[None, :]
            d2 = jnp.maximum(z2 - 2.0 * (z_s @ cb_s.T) + e2, 0.0)
            d = jnp.sqrt(d2 + 1e-12)
            return jnp.mean(d.reshape(-1, n_groups, ng), axis=-1)

        return jax.vmap(jax.vmap(per_slice, in_axes=(1, 0), out_axes=1))(
            zsl, csl)                                   # (R, P, S, G)
    e2 = jnp.sum(cb * cb, -1)                           # (R, K)
    cross = jnp.einsum("rpm,rkm->rpk", zf, cb)
    return e2[:, None, :] - 2.0 * cross


def encode_codes_ref(z, codebooks, *, bits: int, n_groups: int = 1,
                     n_slices: int = 1):
    """(R, P, M) latents + (R, K, M) per-record codebooks ->
    (words (R*nW, W) uint32, counts (R, K), sums (R, K, M)).

    Unfused oracle for kernels/encode_codes.py: per record, quantize
    against that record's codebook (the argmin of
    :func:`encode_scores_ref`), pack each record's codes into its own
    zero-padded word stream, and segment-sum the Eq. 7-8 EMA statistics
    onto representative atoms (``g*ng + ng//2``; plain VQ: the atom).
    """
    R, P, M = z.shape
    K = codebooks.shape[1]
    zf = z.astype(jnp.float32)
    idx = jnp.argmin(encode_scores_ref(z, codebooks, n_groups=n_groups,
                                       n_slices=n_slices),
                     axis=-1).astype(jnp.int32)         # (R, P[, S])
    if n_groups > 1 or n_slices > 1:
        ng = K // n_groups
        rep = idx * ng + ng // 2
        votes = jnp.broadcast_to(zf[:, :, None, :], idx.shape + (M,))
    else:
        rep = idx
        votes = zf
    counts = jax.vmap(lambda r: jax.ops.segment_sum(
        jnp.ones_like(r.reshape(-1), jnp.float32), r.reshape(-1), K))(rep)
    sums = jax.vmap(lambda v, r: jax.ops.segment_sum(
        v.reshape(-1, M), r.reshape(-1), K))(votes, rep)
    # per-record pack, vectorized: every record zero-pads to whole
    # super-groups, so padding each record's flat codes to nW*G and
    # flattening IS the concatenation of the per-record streams
    from .pack_bits import packing_dims
    G, _ = packing_dims(bits)
    flat = idx.reshape(R, -1)
    pad = (-flat.shape[1]) % G
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    words = pack_codes_ref(flat, bits=bits)
    return words, counts, sums


def selective_scan_ref(decay, inp, c, h0):
    """Naive sequential reference: h_t = d_t h_{t-1} + i_t; y_t = <h_t, c_t>.

    decay/inp (B,T,di,N); c (B,T,N); h0 (B,di,N) -> (y (B,T,di), h_last).
    """
    def step(h, xs):
        d, i, ct = xs
        h = d * h + i
        y = jnp.einsum("bdn,bn->bd", h, ct)
        return h, y

    d = jnp.moveaxis(decay.astype(jnp.float32), 1, 0)
    i = jnp.moveaxis(inp.astype(jnp.float32), 1, 0)
    ct = jnp.moveaxis(c.astype(jnp.float32), 1, 0)
    h_last, ys = jax.lax.scan(step, h0.astype(jnp.float32), (d, i, ct))
    return jnp.moveaxis(ys, 0, 1), h_last
