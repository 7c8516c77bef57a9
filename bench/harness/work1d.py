"""Operations of the speech DVQ-AE, computed from shapes, by the
conventions of ``work.py``: a multiply-add is 2 operations; a stride-s,
width-k, SAME 1-D convolution costs ``2 * out_positions * c_in * c_out *
k``; the backward pass of a layer costs its weight gradient plus its
input gradient, except that the first layer needs no input gradient;
elementwise work (activations, instance norm, the x2 nearest upsample,
the optimizer update) is not counted. The quantizer and the encode are
``work.py``'s, per latent position.
"""
from __future__ import annotations

from . import work


def _conv(out_pos, c_in, c_out, k):
    return 2 * out_pos * c_in * c_out * k


def _halve(n: int) -> int:
    return -(-n // 2)               # a stride-2 SAME conv's output length


def positions(model: dict, samples: int) -> int:
    """Latent positions of one clip of ``samples`` samples (T/4)."""
    return _halve(_halve(samples))


def encoder_layers(model: dict, samples: int):
    """Forward operations of each encoder layer for one clip, in order."""
    C, h, M = model["in_channels"], model["hidden"], model["latent_dim"]
    p2, p4 = _halve(samples), positions(model, samples)
    layers = [_conv(p2, C, h // 2, 4), _conv(p4, h // 2, h, 4),
              _conv(p4, h, h, 3)]
    layers += [_conv(p4, h, h, 3), _conv(p4, h, h, 1)] * model["n_res_blocks"]
    return layers + [_conv(p4, h, M, 1)]


def decoder_layers(model: dict, samples: int):
    """Forward operations of each decoder layer for one clip, in order;
    each upsampling conv runs at the doubled length."""
    C, h, M = model["in_channels"], model["hidden"], model["latent_dim"]
    p4 = positions(model, samples)
    layers = [_conv(p4, M, h, 3)]
    layers += [_conv(p4, h, h, 3), _conv(p4, h, h, 1)] * model["n_res_blocks"]
    return layers + [_conv(2 * p4, h, h // 2, 3), _conv(4 * p4, h // 2, C, 3)]


def client_ops(model: dict, samples: int, clips: int) -> int:
    """Model operations of one client's round: one fine-tune step
    (forward and backward of encoder and decoder, the forward's
    quantizer), one encoder pass, and the encode (match + EMA sums)."""
    enc, dec = encoder_layers(model, samples), decoder_layers(model, samples)
    T = positions(model, samples)
    fwd = sum(enc) + sum(dec) + work.quantize_ops(model, T)
    bwd = 2 * (sum(enc) + sum(dec)) - enc[0]
    per_clip = fwd + bwd + sum(enc) + work.encode_ops(model, T)
    return clips * per_clip
