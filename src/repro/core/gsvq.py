"""Group and Sliced Vector Quantization (OCTOPUS §2.4, Eq. 2-3).

GVQ: the codebook (K, M) is partitioned into G groups of N_g = K/G atoms.
A latent vector is matched to the *group* with the smallest mean atom
distance (Eq. 2), then quantized to the inverse-distance-weighted average of
that group's atoms (Eq. 3). This softens the hard-argmin mismatch under
non-IID drift: a slightly-off query still lands in the right neighbourhood.

SVQ: atoms and latents are sliced into n_c parts along M and VQ runs
independently per slice — effective codebook size K^{n_c} at K·M storage.

Transmission: GVQ sends the group index (log2 G bits) per position per
slice; the weighted combination is reconstructed server-side from the shared
codebook, so only indices travel (same contract as plain VQ).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax


class GSVQOut(NamedTuple):
    quantized: jax.Array       # STE-passthrough quantized latents (..., M)
    indices: jax.Array         # (..., n_c) int32 group indices per slice
    codebook_loss: jax.Array
    commit_loss: jax.Array


def _distances(z, codebook):
    """Euclidean distance of every row to every atom: the (N, K) matrix
    that Eq. 2 averages and Eq. 3 weights.

    z: (N, m); codebook: (K, m) -> (N, K). The cross term runs at
    ``HIGHEST``, as the encode kernel's match does. Rounding can take the
    expanded square below zero; ``where`` (not ``maximum``) clamps it, so
    the backward pass carries no tie mask.
    """
    z2 = jnp.sum(jnp.square(z), axis=-1, keepdims=True)
    e2 = jnp.sum(jnp.square(codebook), axis=-1)[None, :]
    cross = jnp.matmul(z, codebook.T, precision=lax.Precision.HIGHEST)
    d2 = z2 - 2.0 * cross + e2
    return jnp.sqrt(jnp.where(d2 > 0.0, d2, 0.0) + 1e-12)


def _match_groups(d, n_groups: int):
    """Eq. 2: the group of least mean atom distance. d: (N, K) -> (N,)."""
    gd = jnp.mean(d.reshape(d.shape[0], n_groups, -1), axis=-1)  # (N, G)
    return jnp.argmin(lax.stop_gradient(gd), axis=-1).astype(jnp.int32)


def gsvq_quantize(z_e, codebook, *, n_groups: int = 1, n_slices: int = 1) -> GSVQOut:
    """Group + sliced quantization with STE.

    z_e: (..., M); codebook: (K, M). M must divide by n_slices, K by n_groups.
    """
    *lead, M = z_e.shape
    K = codebook.shape[0]
    assert M % n_slices == 0, (M, n_slices)
    assert K % n_groups == 0, (K, n_groups)
    m = M // n_slices
    group_of = jnp.arange(K, dtype=jnp.int32) // (K // n_groups)  # (K,)

    zf = z_e.reshape(-1, n_slices, m)                            # (N, n_c, m)
    cb = codebook.reshape(K, n_slices, m).transpose(1, 0, 2)     # (n_c, K, m)

    def per_slice(z_s, cb_s):
        d = _distances(z_s, cb_s)                                # (N, K)
        gidx = _match_groups(d, n_groups)                        # (N,)
        # Eq. 3 from the same matrix: inverse-distance weights, exactly
        # zero outside the matched group, normalised after the matmul
        w = jnp.where(group_of == gidx[:, None], 1.0 / (d + 1e-8), 0.0)
        zq = (jnp.matmul(w, cb_s, precision=lax.Precision.HIGHEST)
              / jnp.sum(w, axis=-1, keepdims=True))
        return zq, gidx

    zq, gidx = jax.vmap(per_slice, in_axes=(1, 0), out_axes=(1, 1))(zf, cb)
    zq = zq.reshape(*lead, M)
    gidx = gidx.reshape(*lead, n_slices)

    codebook_loss = jnp.mean(jnp.square(jax.lax.stop_gradient(z_e) - zq))
    commit_loss = jnp.mean(jnp.square(z_e - jax.lax.stop_gradient(zq)))
    z_st = z_e + jax.lax.stop_gradient(zq - z_e)
    return GSVQOut(quantized=z_st, indices=gidx,
                   codebook_loss=codebook_loss, commit_loss=commit_loss)


def gsvq_indices(z_e, codebook, *, n_groups: int = 1, n_slices: int = 1):
    """Index-only GSVQ match: (..., M) latents -> (..., n_c) int32 group
    indices per slice, identical to ``gsvq_quantize(...).indices`` (same
    Eq. 2 argmin) without building the Eq. 3 weighted average — the
    transmit/refresh path needs only the codes.
    """
    *lead, M = z_e.shape
    K = codebook.shape[0]
    m = M // n_slices
    zf = z_e.reshape(-1, n_slices, m)
    cb = codebook.reshape(K, n_slices, m).transpose(1, 0, 2)

    def per_slice(z_s, cb_s):
        return _match_groups(_distances(z_s, cb_s), n_groups)

    gidx = jax.vmap(per_slice, in_axes=(1, 0), out_axes=1)(zf, cb)
    return gidx.reshape(*lead, n_slices)


def gsvq_dequantize_indices(indices, codebook, z_hint=None, *, n_groups: int,
                            n_slices: int):
    """Server-side reconstruction from group indices.

    Without the original z the exact Eq. 3 weights are unknown; the paper
    transmits indices only, so the server reconstructs with the *uniform*
    group average (the weights' expectation), or — when the client also
    ships a low-rate z hint — the weighted version. indices: (..., n_c).
    """
    *lead, n_c = indices.shape
    K, M = codebook.shape
    m = M // n_slices
    ng = K // n_groups
    cb = codebook.reshape(K, n_slices, m).transpose(1, 0, 2)     # (n_c, K, m)
    groups = cb.reshape(n_slices, n_groups, ng, m)
    flat_idx = indices.reshape(-1, n_c)

    def per_slice(idx_s, groups_s):
        atoms = groups_s[idx_s]                                  # (N, N_g, m)
        return jnp.mean(atoms, axis=1)

    out = jax.vmap(per_slice, in_axes=(1, 0), out_axes=1)(flat_idx, groups)
    return out.reshape(*lead, M)


def gsvq_group_mean_table(codebook, *, n_groups: int, n_slices: int):
    """Precomputed uniform group means: (n_slices, n_groups, m).

    Row ``(s, g)`` is the mean of group ``g``'s atoms restricted to slice
    ``s`` — exactly what :func:`gsvq_dequantize_indices` computes per
    index, hoisted out so the server's fused decode kernel
    (kernels/decode_codes.py) can gather one m-dim row per code instead
    of materialising the (N, N_g, m) atom tensor.
    """
    K, M = codebook.shape
    m = M // n_slices
    ng = K // n_groups
    cb = codebook.reshape(K, n_slices, m).transpose(1, 0, 2)     # (n_c, K, m)
    return jnp.mean(cb.reshape(n_slices, n_groups, ng, m), axis=2)


def gsvq_bits_per_position(n_groups: int, n_slices: int) -> int:
    """Uplink bits per latent position (§2.8): ``n_slices`` group indices
    of ``ceil(log2 n_groups)`` bits each (1-bit floor; the alphabet is
    the group id even when n_groups == 1)."""
    import math
    return n_slices * max(1, math.ceil(math.log2(max(n_groups, 2))))
