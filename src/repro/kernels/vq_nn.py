"""Pallas TPU kernel: VQ nearest-neighbour codebook search.

The OCTOPUS per-sample hot spot: for N latent vectors z (N, M) find the
nearest of K codebook atoms e (K, M) under L2. GPU ports do a per-vector
scan; on TPU we use the expanded form

    ||z - e||^2 = ||z||^2 - 2 z.e^T + ||e||^2

so the dominant term is an (N_blk, M) x (M, K_blk) matmul that runs on the
MXU, with a *streaming argmin* across K blocks (flash-attention style: carry
the running best distance + index, never materialise the (N, K) matrix in
HBM). ||z||^2 is constant per row and dropped from the argmin.

Grid: (N // BLOCK_N, K // BLOCK_K); K is the minor (fastest) grid axis so
each N block sees K blocks in sequence and the carry lives in VMEM scratch.

Block shapes are (8,128)-aligned for VREG/MXU tiling. M is loaded whole
(codebook atom dims here are small: 64-256).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_N = 256
BLOCK_K = 512


def _vq_nn_kernel(z_ref, e_ref, e2_ref, idx_ref, best_ref, bestidx_ref, *,
                  block_k):
    """One (n_block, k_block) tile.

    z_ref:   (BLOCK_N, M) queries            [VMEM]
    e_ref:   (BLOCK_K, M) codebook tile      [VMEM]
    e2_ref:  (1, BLOCK_K) its squared norms  [VMEM]
    idx_ref: (BLOCK_N, 1) output indices     [VMEM] (written on last k step)
    best_ref/bestidx_ref: (BLOCK_N, 1) VMEM scratch carried across the K
    grid axis.
    """
    kstep = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(kstep == 0)
    def _init():
        best_ref[...] = jnp.full_like(best_ref, jnp.inf)
        bestidx_ref[...] = jnp.zeros_like(bestidx_ref)

    z = z_ref[...].astype(jnp.float32)                    # (N, M)
    e = e_ref[...].astype(jnp.float32)                    # (K_blk, M)
    # distance sans ||z||^2 (row-constant): ||e||^2 - 2 z e^T
    e2 = e2_ref[...]                                      # (1, K_blk)
    cross = jax.lax.dot_general(                          # MXU matmul
        z, e, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,              # no bf16 rounding
        preferred_element_type=jnp.float32)               # (N, K_blk)
    d = e2 - 2.0 * cross

    local_best = jnp.min(d, axis=-1, keepdims=True)       # (N, 1)
    local_arg = (jnp.argmin(d, axis=-1, keepdims=True).astype(jnp.int32)
                 + kstep * block_k)

    prev_best = best_ref[...]
    prev_idx = bestidx_ref[...]
    take_new = local_best < prev_best
    best_ref[...] = jnp.where(take_new, local_best, prev_best)
    bestidx_ref[...] = jnp.where(take_new, local_arg, prev_idx)

    @pl.when(kstep == nk - 1)
    def _done():
        idx_ref[...] = bestidx_ref[...]


@functools.partial(jax.jit, static_argnames=("block_n", "block_k", "interpret"))
def vq_nearest_pallas(z, codebook, *, block_n: int = BLOCK_N,
                      block_k: int = BLOCK_K, interpret: bool = False):
    """z: (N, M) float; codebook: (K, M) -> (N,) int32 nearest-atom indices.

    N and K are padded to block multiples; M loaded unblocked.
    """
    N, M = z.shape
    K, M2 = codebook.shape
    assert M == M2, (M, M2)
    block_n = min(block_n, max(8, N))
    block_k = min(block_k, max(128, K))
    pad_n = (-N) % block_n
    pad_k = (-K) % block_k
    zp = jnp.pad(z, ((0, pad_n), (0, 0))) if pad_n else z
    # pad codebook with +inf-distance atoms (huge norm keeps them unselected)
    ep = jnp.pad(codebook, ((0, pad_k), (0, 0)), constant_values=1e30) \
        if pad_k else codebook
    Np, Kp = N + pad_n, K + pad_k
    # lane-major row norms: reducing the tile in-kernel would need a
    # sublane -> lane relayout per grid step
    e2 = jnp.sum(ep.astype(jnp.float32) ** 2, axis=-1)[None, :]

    grid = (Np // block_n, Kp // block_k)
    out = pl.pallas_call(
        functools.partial(_vq_nn_kernel, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, M), lambda n, k: (n, 0)),
            pl.BlockSpec((block_k, M), lambda n, k: (k, 0)),
            pl.BlockSpec((1, block_k), lambda n, k: (0, k)),
        ],
        out_specs=pl.BlockSpec((block_n, 1), lambda n, k: (n, 0)),
        out_shape=jax.ShapeDtypeStruct((Np, 1), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.int32),
        ],
        interpret=interpret,
    )(zp, ep, e2)
    return out[:N, 0]
