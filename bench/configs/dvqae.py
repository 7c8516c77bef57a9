"""Plain reference of a DVQ-AE client round and the server's decode and
merge (OCTOPUS, arXiv:2105.00602, Sec. 2.3-2.6), for the configurations
``dvqae_image_vq`` and ``dvqae_image_gsvq``.

Straightforward ``jax.numpy``/``lax`` at a stated precision, one client
at a time (vmapped), with no kernels, no packing and no batching tricks.
It imports nothing of the program under test: the benchmark makes the
weights here, from the seed, and hands the same arrays to the program.

A client round, as the configuration states it:

1. deploy the global weights; one AdamW step (b1 0.9, b2 0.95, eps 1e-8,
   no decay) on the encoder and decoder, codebook frozen, of the Eq. 6
   loss: reconstruction + alpha codebook + beta commitment + lambda
   latent terms, where the quantizer sees the instance-normalised latent
   (Eq. 4-5) and the decoder gets Z. + the per-instance mean residual;
2. one encoder pass with the new weights, instance-normalised;
3. each latent's code: the nearest atom (VQ), or per slice the group
   with the least mean Euclidean distance to its atoms (GSVQ, Eq. 2);
4. per-atom counts and latent sums (Eq. 7-8; a GSVQ code votes its
   position's whole latent onto its group's middle atom) and one EMA
   step from counts 1 and the deployed codebook (Eq. 9, Laplace eps 1e-5).

The server merges the clients' EMA codebooks weighted by their counts.
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

LAPLACE_EPS = 1e-5
IN_EPS = 1e-5


# ------------------------------------------------------------------ weights

def _conv_init(key, c_in, c_out, k):
    s = 1.0 / math.sqrt(c_in * k * k)
    k1, _ = jax.random.split(key)
    return {"kernel": jax.random.uniform(k1, (k, k, c_in, c_out),
                                         jnp.float32, -s, s),
            "bias": jnp.zeros((c_out,), jnp.float32)}


def _res_init(key, c):
    k1, k2 = jax.random.split(key)
    return {"c1": _conv_init(k1, c, c, 3), "c2": _conv_init(k2, c, c, 1)}


def init_params(key, model: dict) -> dict:
    """Seeded float32 weights in the layout the image DVQ-AE keeps:
    uniform(+-1/sqrt(fan_in)) kernels, zero biases, a unit-normal
    codebook (the latents it matches are instance-normalised)."""
    C, h, M = model["in_channels"], model["hidden"], model["latent_dim"]
    n_res = model["n_res_blocks"]
    ke, kd, kc = jax.random.split(key, 3)
    ks = jax.random.split(ke, 4 + n_res)
    enc = {"down1": _conv_init(ks[0], C, h // 2, 4),
           "down2": _conv_init(ks[1], h // 2, h, 4),
           "mid": _conv_init(ks[2], h, h, 3),
           "to_latent": _conv_init(ks[3], h, M, 1)}
    for i in range(n_res):
        enc[f"res{i}"] = _res_init(ks[4 + i], h)
    ks = jax.random.split(kd, 4 + n_res)
    dec = {"from_latent": _conv_init(ks[0], M, h, 3),
           "up1": _conv_init(ks[1], h, h // 2, 4),
           "up2": _conv_init(ks[2], h // 2, C, 4)}
    for i in range(n_res):
        dec[f"res{i}"] = _res_init(ks[3 + i], h)
    cb = jax.random.normal(kc, (model["codebook_size"], M), jnp.float32)
    return {"encoder": enc, "decoder": dec, "codebook": cb}


# ------------------------------------------------------------------ network

class Numerics(NamedTuple):
    """The precision a round is computed at."""
    conv: lax.Precision          # convolutions' matmul precision
    match: lax.Precision         # the codebook match and Eq. 3 average
    fp8: bool = False            # conv operands rounded to float8 e4m3


NUMERICS = {
    # the plain reference: float32 throughout at HIGHEST
    "reference": Numerics(lax.Precision.HIGHEST, lax.Precision.HIGHEST),
    # what the configuration states: float32 storage, convolutions at the
    # default precision (one bf16 MXU pass on TPU), the match at HIGHEST
    "stated": Numerics(lax.Precision.DEFAULT, lax.Precision.HIGHEST),
    # the control, one step below the stated precision in each part:
    # float8 conv operands (per-tensor scale) and a three-pass match
    "control": Numerics(lax.Precision.DEFAULT, lax.Precision.HIGH,
                        fp8=True),
}


def _fp8(x):
    """Round to float8 e4m3 under a per-tensor scale (max |x| -> 448)."""
    s = lax.stop_gradient(jnp.max(jnp.abs(x))) / 448.0 + 1e-30
    return (x / s).astype(jnp.float8_e4m3fn).astype(x.dtype) * s


def _conv(p, x, num, stride=1):
    k = p["kernel"].astype(x.dtype)
    if num.fp8:
        x, k = _fp8(x), _fp8(k)
    y = lax.conv_general_dilated(
        x, k, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=num.conv)
    return y + p["bias"].astype(x.dtype)


def _conv_t(p, x, num):
    k = p["kernel"].astype(x.dtype)
    if num.fp8:
        x, k = _fp8(x), _fp8(k)
    y = lax.conv_transpose(
        x, k, (2, 2), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=num.conv)
    return y + p["bias"].astype(x.dtype)


def _inorm(x, axes):
    mu = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=axes, keepdims=True)
    return (x - mu) / jnp.sqrt(var + IN_EPS)


def _res(p, x, num):
    h = _conv(p["c1"], jax.nn.relu(x), num)
    return x + _conv(p["c2"], jax.nn.relu(h), num)


def encode(enc, model, x, num):
    """(B, H, W, C) images -> (B, H/4 * W/4, M) latents."""
    h = jax.nn.relu(_conv(enc["down1"], x, num, 2))
    if model["encoder_in"]:
        h = _inorm(h, (1, 2))
    h = jax.nn.relu(_conv(enc["down2"], h, num, 2))
    if model["encoder_in"]:
        h = _inorm(h, (1, 2))
    h = _conv(enc["mid"], h, num)
    for i in range(model["n_res_blocks"]):
        h = _res(enc[f"res{i}"], h, num)
    z = _conv(enc["to_latent"], jax.nn.relu(h), num)
    B, H, W, M = z.shape
    return z.reshape(B, H * W, M)


def decode(dec, model, z, num):
    B, T, M = z.shape
    s = int(round(math.sqrt(T)))
    h = _conv(dec["from_latent"], z.reshape(B, s, s, M), num)
    for i in range(model["n_res_blocks"]):
        h = _res(dec[f"res{i}"], h, num)
    h = jax.nn.relu(_conv_t(dec["up1"], jax.nn.relu(h), num))
    return _conv_t(dec["up2"], h, num)


def is_gsvq(model) -> bool:
    return model["n_groups"] > 1 or model["n_slices"] > 1


def scores(z, codebook, model, num):
    """Match scores, lowest wins. VQ: ||e||^2 - 2 z.e, (..., K).
    GSVQ: per slice, the mean over each group's atoms of the Euclidean
    distance, (..., S, G)."""
    cb = codebook.astype(z.dtype)
    if not is_gsvq(model):
        cross = jnp.einsum("...m,km->...k", z, cb, precision=num.match)
        return jnp.sum(cb * cb, -1) - 2.0 * cross
    S, G = model["n_slices"], model["n_groups"]
    K, M = cb.shape
    m = M // S
    zs = z.reshape(z.shape[:-1] + (S, m))
    cs = cb.reshape(K, S, m)
    cross = jnp.einsum("...sm,ksm->...sk", zs, cs, precision=num.match)
    d2 = (jnp.sum(zs * zs, -1)[..., None] - 2.0 * cross
          + jnp.sum(cs * cs, -1).T)
    d = jnp.sqrt(jnp.maximum(d2, 0.0) + 1e-12)
    return jnp.mean(d.reshape(d.shape[:-1] + (G, K // G)), axis=-1)


def _quantize(z, codebook, model, num):
    """Quantised latents as the fine-tune's forward pass sees them."""
    idx = jnp.argmin(lax.stop_gradient(scores(z, codebook, model, num)),
                     axis=-1)
    cb = codebook.astype(z.dtype)
    if not is_gsvq(model):
        return cb[idx]
    S, G = model["n_slices"], model["n_groups"]
    K, M = cb.shape
    m, ng = M // S, K // G
    groups = cb.reshape(G, ng, S, m)                  # atom g*ng+j, slice s
    atoms = jnp.moveaxis(groups, 2, 0)[jnp.arange(S), idx]  # (..., S, ng, m)
    zs = z.reshape(z.shape[:-1] + (S, 1, m))
    d = jnp.sqrt(jnp.sum(jnp.square(zs - atoms), -1) + 1e-12)
    w = 1.0 / (d + 1e-8)
    w = w / jnp.sum(w, -1, keepdims=True)
    zq = jnp.einsum("...sg,...sgm->...sm", w, atoms, precision=num.match)
    return zq.reshape(z.shape)


def loss(enc_dec, codebook, model, x, num):
    """Eq. 6 with the quantizer on IN(z_e) and a straight-through path."""
    z_e = encode(enc_dec["encoder"], model, x, num)
    z_in = _inorm(z_e, (-2,)) if model["apply_in"] else z_e
    z_q = _quantize(z_in, codebook, model, num)
    sg = lax.stop_gradient
    codebook_loss = jnp.mean(jnp.square(sg(z_in) - z_q))
    commit = jnp.mean(jnp.square(z_in - sg(z_q)))
    z_st = z_in + sg(z_q - z_in)
    private = jnp.mean(z_e - sg(z_st), axis=-2, keepdims=True)
    latent = jnp.mean(jnp.square(z_in - sg(z_q)))
    x_rec = decode(enc_dec["decoder"], model, z_st + private, num)
    recon = jnp.mean(jnp.square(x - x_rec))
    return (recon + model["alpha"] * codebook_loss + model["beta"] * commit
            + model["lam"] * latent)


def adamw_first_step(params, grads, lr, b1=0.9, b2=0.95, eps=1e-8):
    """AdamW's first step from zero moments (no weight decay)."""
    def upd(p, g):
        g = g.astype(jnp.float32)
        m = (1 - b1) * g
        v = (1 - b2) * jnp.square(g)
        step = (m / (1 - b1)) / (jnp.sqrt(v / (1 - b2)) + eps)
        return (p - lr * step.astype(p.dtype)).astype(p.dtype)
    return jax.tree.map(upd, params, grads)


def stats(z, codes, model):
    """Eq. 7-8 counts (K,) and latent sums (K, M) of one client."""
    K = model["codebook_size"]
    M = z.shape[-1]
    zf = z.reshape(-1, M).astype(jnp.float32)
    if is_gsvq(model):
        ng = K // model["n_groups"]
        atom = codes * ng + ng // 2                          # (P, S)
        S = atom.shape[-1]
        atom = atom.reshape(-1)
        zf = jnp.repeat(zf, S, axis=0)
    else:
        atom = codes.reshape(-1)
    n = jax.ops.segment_sum(jnp.ones_like(atom, jnp.float32), atom, K)
    s = jax.ops.segment_sum(zf, atom, K)
    return n, s


def ema(codebook, n, s, gamma):
    """One EMA step from a fresh deploy (counts 1, sums = codebook)."""
    K = codebook.shape[0]
    counts = gamma + (1.0 - gamma) * n
    sums = gamma * codebook.astype(jnp.float32) + (1.0 - gamma) * s
    total = jnp.sum(counts)
    smoothed = (counts + LAPLACE_EPS) / (total + K * LAPLACE_EPS) * total
    return counts, sums / smoothed[:, None]


def client_round(params, model, client, x, sent_codes, num):
    """One client's round (module docstring) at the numerics ``num``, and
    how the codes the program sent for it (``sent_codes``) look from it.

    Returns a dict: its own ``codes``, EMA ``counts`` (K,) and
    ``codebook`` (K, M); for the sent codes their widest and summed score
    gap above the best (``gap_max``, ``gap_sum``), how many differ from
    its own (``mismatches``), and the Eq. 7-8 counts and latent sums they
    assign (``sent_n``, ``sent_s``): its latents, their codes."""
    enc_dec = {"encoder": params["encoder"], "decoder": params["decoder"]}
    grads = jax.grad(loss)(enc_dec, params["codebook"], model, x, num)
    new = adamw_first_step(enc_dec, grads, client["lr"])
    z = encode(new["encoder"], model, x, num)
    if model["apply_in"]:
        z = _inorm(z, (-2,))
    z = z.reshape(-1, z.shape[-1])                           # (P, M)
    sc = scores(z, params["codebook"], model, num)
    codes = jnp.argmin(sc, axis=-1).astype(jnp.int32)
    n, s = stats(z, codes, model)
    counts, cb = ema(params["codebook"], n, s, client["gamma"])
    sent = sent_codes.reshape(codes.shape)
    got = jnp.take_along_axis(sc, sent[..., None], axis=-1)[..., 0]
    gap = got - jnp.min(sc, axis=-1)
    sent_n, sent_s = stats(z, sent, model)
    return {"codes": codes, "counts": counts, "codebook": cb,
            "gap_max": jnp.max(gap), "gap_sum": jnp.sum(gap),
            "mismatches": jnp.sum(sent != codes),
            "sent_n": sent_n, "sent_s": sent_s}


def batched_round(model: dict, client: dict, numerics: str = "reference"):
    """A jitted ``client_round`` over a block of clients at the named
    ``NUMERICS``: (params, x (C, B, H, W, Ch), sent codes (C, ...)) ->
    stacked outputs."""
    one = partial(client_round, model=model, client=client,
                  num=NUMERICS[numerics])
    return jax.jit(jax.vmap(lambda p, x, c: one(p, x=x, sent_codes=c),
                            in_axes=(None, 0, 0)))


def merge(counts: np.ndarray, codebooks: np.ndarray):
    """Count-weighted sum terms of the server merge: (num (K, M), den (K,))
    in float64, to be summed over blocks and divided once."""
    c = np.asarray(counts, np.float64)
    return (np.einsum("ck,ckm->km", c, np.asarray(codebooks, np.float64)),
            c.sum(axis=0))


def ema_weights(n: np.ndarray, gamma: float):
    """Per client and atom, from the code counts ``n`` (C, K) of its
    round: its EMA count and the factor count / Laplace-smoothed count
    that the merge's weighted sum applies to its EMA latent sums."""
    n = np.asarray(n, np.float64)
    K = n.shape[-1]
    counts = gamma + (1.0 - gamma) * n
    total = gamma * K + (1.0 - gamma) * n.sum(axis=-1, keepdims=True)
    smoothed = (counts + LAPLACE_EPS) / (total + K * LAPLACE_EPS) * total
    return counts, counts / smoothed


# ---------------------------------------------------------- wire and decode

def packing(bits: int):
    """(codes, words) per super-group: lcm(bits, 32) bits."""
    lcm = bits * 32 // math.gcd(bits, 32)
    return lcm // bits, lcm // 32


def unpack(words, bits: int, n_records: int, per_record: int) -> np.ndarray:
    """A packed word stream of ``n_records`` records, each padded to whole
    super-groups, -> (n_records, per_record) codes. Code j of a group
    sits at bits [j*bits, (j+1)*bits) of the group's words, low first."""
    G, W = packing(bits)
    w = np.asarray(words, np.uint64).reshape(-1, W)
    mask = np.uint64((1 << bits) - 1)
    cols = []
    for j in range(G):
        w0, s = divmod(j * bits, 32)
        v = w[:, w0] >> np.uint64(s)
        if s + bits > 32:
            v = v | (w[:, w0 + 1] << np.uint64(32 - s))
        cols.append(v & mask)
    flat = np.stack(cols, axis=1).reshape(n_records, -1)
    return flat[:, :per_record].astype(np.int64)


def code_bits(model) -> int:
    n = model["n_groups"] if is_gsvq(model) else model["codebook_size"]
    return max(1, math.ceil(math.log2(max(n, 2))))


def decode_rows(codes: np.ndarray, codebook, model) -> np.ndarray:
    """VQ codes (...) -> feature rows (..., M): each code's atom."""
    if is_gsvq(model):
        raise NotImplementedError("the plain gather is written for VQ; no "
                                  "GSVQ cell decodes")
    return np.asarray(codebook, np.float32)[codes]
