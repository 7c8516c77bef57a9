"""Operations and bytes the algorithm needs, computed from shapes.

These count what a client round, an encode and a decode must do, not
what one implementation does: a matmul run at ``Precision.HIGHEST`` (six
bf16 passes on the MXU) counts once, a one-hot gather counts as a
gather (no operations, its bytes), and elementwise work (activations,
normalisation, the optimizer update) is not counted. So a later kernel
that does the same work is read against the same numbers.

Conventions: a multiply-add is 2 operations; a stride-s, k x k, SAME
convolution costs ``2 * out_positions * c_in * c_out * k^2``; a
transposed convolution ``2 * in_positions * c_in * c_out * k^2``; the
backward pass of a layer costs its weight gradient plus its input
gradient (each as much as the forward), except that the first layer
needs no input gradient.
"""
from __future__ import annotations

import math


def _conv(out_pos, c_in, c_out, k):
    return 2 * out_pos * c_in * c_out * k * k


def encoder_layers(model: dict, image: int):
    """Forward operations of each encoder layer for one image, in order."""
    C, h, M = model["in_channels"], model["hidden"], model["latent_dim"]
    p2, p4 = (image // 2) ** 2, (image // 4) ** 2
    layers = [_conv(p2, C, h // 2, 4), _conv(p4, h // 2, h, 4),
              _conv(p4, h, h, 3)]
    layers += [_conv(p4, h, h, 3), _conv(p4, h, h, 1)] * model["n_res_blocks"]
    return layers + [_conv(p4, h, M, 1)]


def decoder_layers(model: dict, image: int):
    C, h, M = model["in_channels"], model["hidden"], model["latent_dim"]
    p2, p4 = (image // 2) ** 2, (image // 4) ** 2
    layers = [_conv(p4, M, h, 3)]
    layers += [_conv(p4, h, h, 3), _conv(p4, h, h, 1)] * model["n_res_blocks"]
    # transposed convs: 2 * input positions * c_in * c_out * k^2
    return layers + [_conv(p4, h, h // 2, 4), _conv(p2, h // 2, C, 4)]


def positions(model: dict, image: int) -> int:
    return (image // 4) ** 2


def match_ops(model: dict, n: int) -> int:
    """Matching ``n`` latents against the codebook: the distances
    (2*K*M per latent); GSVQ adds a root and a pooling add per atom and
    slice."""
    K, M = model["codebook_size"], model["latent_dim"]
    ops = 2 * n * K * M
    if is_gsvq(model):
        ops += 2 * n * model["n_slices"] * K
    return ops


def quantize_ops(model: dict, n: int) -> int:
    """The fine-tune forward's quantizer: the match, and for GSVQ the
    Eq. 3 inverse-distance average over the matched group (distances
    and the weighted sum, 5 operations per atom element)."""
    ops = match_ops(model, n)
    if is_gsvq(model):
        K, M = model["codebook_size"], model["latent_dim"]
        ng, m = K // model["n_groups"], M // model["n_slices"]
        ops += 5 * n * model["n_slices"] * ng * m
    return ops


def client_ops(model: dict, image: int, samples: int) -> int:
    """Model operations of one client's round: one fine-tune step
    (forward and backward of encoder and decoder, the forward's
    quantizer), one encoder pass, and the encode (match + EMA sums)."""
    enc, dec = encoder_layers(model, image), decoder_layers(model, image)
    T = positions(model, image)
    fwd = sum(enc) + sum(dec) + quantize_ops(model, T)
    bwd = 2 * (sum(enc) + sum(dec)) - enc[0]
    per_image = fwd + bwd + sum(enc) + encode_ops(model, T)
    return samples * per_image


def is_gsvq(model: dict) -> bool:
    return model["n_groups"] > 1 or model["n_slices"] > 1


def codes_per_position(model: dict) -> int:
    return model["n_slices"] if is_gsvq(model) else 1


def code_bits(model: dict) -> int:
    """Bits per code: the group alphabet (GSVQ) or the codebook (VQ)."""
    n = model["n_groups"] if is_gsvq(model) else model["codebook_size"]
    return max(1, math.ceil(math.log2(max(n, 2))))


def packed_bytes(model: dict, n_codes: int) -> int:
    """One record of ``n_codes`` codes, padded to whole super-groups of
    lcm(bits, 32) bits."""
    b = code_bits(model)
    lcm = b * 32 // math.gcd(b, 32)
    groups = -(-n_codes // (lcm // b))
    return groups * lcm // 8


def encode_ops(model: dict, n: int) -> int:
    """Encoding ``n`` latents: the match and the per-atom latent sums."""
    return match_ops(model, n) + n * codes_per_position(model) * \
        model["latent_dim"]


def encode_bytes(model: dict, n: int) -> int:
    """One record of ``n`` latents: read latents and codebook (float32),
    write the packed codes, counts and sums."""
    K, M = model["codebook_size"], model["latent_dim"]
    return (4 * n * M + 4 * K * M + packed_bytes(
        model, n * codes_per_position(model)) + 4 * K + 4 * K * M)


def decode_bytes(model: dict, n: int) -> int:
    """Decoding one record of ``n`` positions: read its packed codes and
    the decode table, write ``n`` float32 feature rows of M."""
    K, M = model["codebook_size"], model["latent_dim"]
    S = codes_per_position(model)
    table = 4 * (S * model["n_groups"] * (M // S) if S > 1 else K * M)
    return packed_bytes(model, n * S) + table + 4 * n * M


def roofline_seconds(ops: float, nbytes: float, peak: dict):
    """Least time on the chip and which bound sets it."""
    t_ops = ops / peak["flops_per_s"]
    t_mem = nbytes / peak["bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
