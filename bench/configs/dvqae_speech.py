"""Plain reference of a speech DVQ-AE client round and the server's merge
(OCTOPUS, arXiv:2105.00602, Sec. 2.3-2.6), for ``dvqae_speech_gsvq``.

The round is the image reference's (``dvqae.py``: one AdamW step of the
Eq. 6 loss with the codebook frozen, one encoder pass, the match, Eq. 7-9
statistics and one EMA step); only the network differs. Its quantizer,
statistics, EMA step, merge, unpacker and numerics are taken from
``dvqae.py`` as they are. It imports nothing of the program under test.

The network, on (B, T, C) raw waveforms, in the layer order of the
program's ``core/dvqae.py`` ``kind="speech"``:

- encoder: Conv1D k4 s2 (C -> h/2), ReLU, IN over time; Conv1D k4 s2
  (h/2 -> h), ReLU, IN over time; Conv1D k3 (h -> h); residual blocks
  (ReLU, k3, ReLU, k1, add); ReLU, Conv1D k1 (h -> M): T/4 latents;
- decoder: Conv1D k3 (M -> h); residual blocks; ReLU, x2 nearest
  upsample, Conv1D k3 (h -> h/2), ReLU; x2 nearest upsample, Conv1D k3
  (h/2 -> C).

Departures from the program, each on purpose: the codebook is drawn
unit-normal here (the benchmark hands these weights to the program, so
its own initialiser never runs); convolutions are written with
``lax.conv_general_dilated`` directly at the named precision; the
control rounds conv operands to float8 as ``dvqae.py`` does.

One departure from ``dvqae.py``: ``ema_weights`` keeps each client's EMA
count in float32, the precision the configuration states for a client's
state, where the image reference keeps it in float64. A client record
here holds 512,000 codes, and a GSVQ code votes its group's middle atom,
so a busy atom gathers some 10^7 votes in a round; float32's 0.01 is
2.2e-10 short of 0.01, which over those votes moves the round's total
by a few tenths of a vote or more, and ``votes_off`` rounds per atom. In
float32 the counts the sent codes imply are the program's to the last
rounding, so ``votes_off`` stays exact: one vote moved reads 2.
"""
from __future__ import annotations

import math
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench.harness.loader import load_module

_image = load_module(Path(__file__).with_name("dvqae.py"), "reference",
                     "dvqae")

# shared with the image reference, unchanged
NUMERICS = _image.NUMERICS
adamw_first_step = _image.adamw_first_step
scores = _image.scores
stats = _image.stats
ema = _image.ema
merge = _image.merge
unpack = _image.unpack
code_bits = _image.code_bits
LAPLACE_EPS = _image.LAPLACE_EPS
is_gsvq = _image.is_gsvq
_inorm = _image._inorm
_quantize = _image._quantize


# ------------------------------------------------------------------ weights

def _conv_init(key, c_in, c_out, k):
    s = 1.0 / math.sqrt(c_in * k)
    return {"kernel": jax.random.uniform(key, (k, c_in, c_out), jnp.float32,
                                         -s, s),
            "bias": jnp.zeros((c_out,), jnp.float32)}


def _res_init(key, c):
    k1, k2 = jax.random.split(key)
    return {"c1": _conv_init(k1, c, c, 3), "c2": _conv_init(k2, c, c, 1)}


def init_params(key, model: dict) -> dict:
    """Seeded float32 weights in the layout the speech DVQ-AE keeps:
    (k, c_in, c_out) kernels uniform(+-1/sqrt(c_in k)), zero biases, a
    unit-normal codebook."""
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
    ks = jax.random.split(kd, 3 + n_res)
    dec = {"from_latent": _conv_init(ks[0], M, h, 3),
           "up1": _conv_init(ks[1], h, h // 2, 3),
           "up2": _conv_init(ks[2], h // 2, C, 3)}
    for i in range(n_res):
        dec[f"res{i}"] = _res_init(ks[3 + i], h)
    cb = jax.random.normal(kc, (model["codebook_size"], M), jnp.float32)
    return {"encoder": enc, "decoder": dec, "codebook": cb}


# ------------------------------------------------------------------ network

def _conv(p, x, num, stride=1):
    k = p["kernel"].astype(x.dtype)
    if num.fp8:
        x, k = _image._fp8(x), _image._fp8(k)
    y = lax.conv_general_dilated(
        x, k, (stride,), "SAME", dimension_numbers=("NHC", "HIO", "NHC"),
        precision=num.conv)
    return y + p["bias"].astype(x.dtype)


def _res(p, x, num):
    h = _conv(p["c1"], jax.nn.relu(x), num)
    return x + _conv(p["c2"], jax.nn.relu(h), num)


def encode(enc, model, x, num):
    """(B, T, C) waveforms -> (B, T/4, M) latents."""
    h = jax.nn.relu(_conv(enc["down1"], x, num, 2))
    if model["encoder_in"]:
        h = _inorm(h, (1,))
    h = jax.nn.relu(_conv(enc["down2"], h, num, 2))
    if model["encoder_in"]:
        h = _inorm(h, (1,))
    h = _conv(enc["mid"], h, num)
    for i in range(model["n_res_blocks"]):
        h = _res(enc[f"res{i}"], h, num)
    return _conv(enc["to_latent"], jax.nn.relu(h), num)


def _upsample(x):
    return jnp.repeat(x, 2, axis=1)


def decode(dec, model, z, num):
    """(B, T/4, M) -> (B, T, C)."""
    h = _conv(dec["from_latent"], z, num)
    for i in range(model["n_res_blocks"]):
        h = _res(dec[f"res{i}"], h, num)
    h = jax.nn.relu(_conv(dec["up1"], _upsample(jax.nn.relu(h)), num))
    return _conv(dec["up2"], _upsample(h), num)


def loss(enc_dec, codebook, model, x, num):
    """Eq. 6 with the quantizer on IN(z_e) over time and a
    straight-through path, as ``dvqae.loss``."""
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


def client_round(params, model, client, x, sent_codes, num):
    """One client's round at the numerics ``num``; the same outputs as
    ``dvqae.client_round``."""
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


def ema_weights(n: np.ndarray, gamma: float):
    """As ``dvqae.ema_weights``, from the code counts ``n`` (C, K) of a
    round, with each EMA count computed in float32 as a client's state
    holds it: ``gamma * 1 + (1 - gamma) * n`` from a fresh deploy."""
    n32 = np.asarray(n, np.float32)
    counts = (np.float32(gamma) + np.float32(1.0 - gamma) * n32).astype(
        np.float64)
    K = counts.shape[-1]
    total = counts.sum(axis=-1, keepdims=True)
    smoothed = (counts + LAPLACE_EPS) / (total + K * LAPLACE_EPS) * total
    return counts, counts / smoothed


def batched_round(model: dict, client: dict, numerics: str = "reference"):
    """A jitted ``client_round`` over a block of clients at the named
    ``NUMERICS``: (params, x (C, B, T, Ch), sent codes (C, ...)) ->
    stacked outputs."""
    one = partial(client_round, model=model, client=client,
                  num=NUMERICS[numerics])
    return jax.jit(jax.vmap(lambda p, x, c: one(p, x=x, sent_codes=c),
                            in_axes=(None, 0, 0)))
