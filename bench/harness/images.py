"""Seeded image inputs, made on the device in one jitted call.

A copy of the repo's procedural generator (content = one of eight
glyphs, style = a per-identity channel gain, bias and background tint,
plus pixel noise), kept here so that the inputs every run sends stay
the same whatever the program's own data module becomes.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

N_SHAPES = 8


def _stencils(size: int):
    r = jnp.linspace(-1.0, 1.0, size)
    yy, xx = jnp.meshgrid(r, r, indexing="ij")
    rad = jnp.sqrt(xx ** 2 + yy ** 2)
    ax, ay = jnp.abs(xx), jnp.abs(yy)
    shapes = [jnp.abs(rad - 0.6) < 0.18, rad < 0.55,
              (ax < 0.6) & (ay < 0.6) & ((ax > 0.35) | (ay > 0.35)),
              (ax < 0.18) | (ay < 0.18), jnp.abs(xx - yy) < 0.22,
              jnp.abs(xx + yy) < 0.22, ay < 0.25, ax < 0.25]
    return jnp.stack(shapes).astype(jnp.float32)


@partial(jax.jit, static_argnames=("n", "size", "channels", "identities"))
def make_images(key, *, n: int, size: int, channels: int,
                identities: int):
    """(n, size, size, channels) float32 images."""
    kc, ks, kn, kg = jax.random.split(key, 4)
    content = jax.random.randint(kc, (n,), 0, N_SHAPES)
    style = jax.random.randint(ks, (n,), 0, identities)
    base = _stencils(size)[content][..., None]
    k1, k2, k3 = jax.random.split(kg, 3)
    gains = 0.5 + jax.random.uniform(k1, (identities, channels))
    bias = 0.3 * jax.random.normal(k2, (identities, channels))
    tint = 0.2 * jax.random.uniform(k3, (identities, channels))
    g = gains[style][:, None, None, :]
    b = bias[style][:, None, None, :]
    t = tint[style][:, None, None, :]
    noise = 0.05 * jax.random.normal(kn, (n, size, size, channels))
    return base * g + (1.0 - base) * t + b + noise


def seed_key(seed: int, purpose: int = 0):
    """A PRNG key from a seed of any size (PRNGKey keeps 32 bits only)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, purpose)
