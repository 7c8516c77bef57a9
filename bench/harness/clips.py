"""Seeded keyword clips, made on the device in one jitted call.

A clip is ``samples`` samples of raw waveform at ``rate`` Hz (one second
in the benchmark) in which one of ``words`` word patterns is spoken by
its client's speaker for ``WORD_SHARE`` of the clip. A word is a run of
``SEGMENTS`` voiced segments, each a fundamental with two formant tones
under a Hann envelope: the segment's intonation (a multiple of the
speaker's pitch), its formant frequencies and its loudness are the
word's. A speaker is a pitch, a gain and a spectral tilt (how loud the
formants are beside the fundamental). Each clip starts its word at an
onset of its own, drawn so that the word ends inside the clip, and
carries a little white noise.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

SEGMENTS = 4
WORD_SHARE = 0.6                # a word's length, of the clip's
NOISE = 0.01


@partial(jax.jit, static_argnames=("blocks", "cohort", "clips", "samples",
                                   "rate", "speakers", "words"))
def make_clips(key, *, blocks: int, cohort: int, clips: int, samples: int,
               rate: int, speakers: int, words: int):
    """A tuple of ``blocks`` arrays (cohort, clips, samples, 1) float32:
    the pool, cut into cohort blocks; each client is one speaker."""
    clip_s = samples / rate
    seg_s = WORD_SHARE * clip_s / SEGMENTS
    kw, ks, kc, kn = jax.random.split(key, 4)

    def uniform(k, shape, lo, hi):
        return jax.random.uniform(k, shape, jnp.float32, lo, hi)

    k = jax.random.split(kw, 4)
    intonation = uniform(k[0], (words, SEGMENTS), 0.8, 1.25)
    f1 = uniform(k[1], (words, SEGMENTS), 300.0, 900.0)
    f2 = uniform(k[2], (words, SEGMENTS), 900.0, 2500.0)
    loud = uniform(k[3], (words, SEGMENTS), 0.3, 1.0)
    k = jax.random.split(ks, 3)
    pitch = uniform(k[0], (speakers,), 90.0, 250.0)
    gain = uniform(k[1], (speakers,), 0.3, 1.0)
    tilt = uniform(k[2], (speakers,), 0.2, 0.9)

    k = jax.random.split(kc, 3)
    speaker = jax.random.randint(k[0], (blocks, cohort), 0, speakers)
    word = jax.random.randint(k[1], (blocks, cohort, clips), 0, words)
    onset = uniform(k[2], (blocks, cohort, clips), 0.0, 1.0 - WORD_SHARE) \
        * clip_s
    t = jnp.arange(samples, dtype=jnp.float32) / rate
    two_pi = 2.0 * jnp.pi

    def block(args):
        """One cohort block, so that no temporary is larger than a few of
        it; every segment and tone in one ``sin`` each, which keeps the
        program small to compile."""
        i, speaker, word, onset = args
        freq = jnp.stack([pitch[speaker][:, None, None] * intonation[word],
                          f1[word], f2[word]], -1)     # (cohort, clips, S, 3)
        amp = tilt[speaker][:, None] ** jnp.arange(3)   # 1, tilt, tilt^2
        tone = jnp.sum(amp[:, None, None, :, None]
                       * jnp.sin(two_pi * freq[..., None] * t), axis=-2)
        u = (t - onset[..., None, None]) / seg_s \
            - jnp.arange(SEGMENTS)[:, None]            # 0..1 inside
        env = jnp.where((u > 0) & (u < 1), jnp.sin(jnp.pi * u) ** 2, 0.0)
        x = jnp.sum(loud[word][..., None] * env * tone, axis=2)
        noise = jax.random.normal(jax.random.fold_in(kn, i), x.shape)
        return gain[speaker][:, None, None] * x + NOISE * noise

    x = jax.lax.map(block, (jnp.arange(blocks), speaker, word, onset))
    return tuple(x[i][..., None] for i in range(blocks))
