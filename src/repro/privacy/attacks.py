"""Inference attacks on captured code streams (red team for §2.5).

"Advances and Open Problems in Federated Learning" (1912.04977) names
inference attacks on transmitted updates as a first-class open problem;
OCTOPUS's §2.5 claim is that its transmitted payloads don't give such an
attacker anything. These attackers test that claim from the attacker's
actual vantage point: NOT decoded latents (the ``privacy_audit`` view),
but the packed :class:`~repro.wire.CodePayload` streams a
:class:`~repro.privacy.tap.PayloadTap` records off the wire.

Both attacks are shadow-classifier attacks over per-sample code
histograms (order-free code usage — the strongest simple statistic of a
discrete stream):

  * ATTRIBUTE inference — predict a sensitive attribute (style /
    speaker / identity) of the sample behind a captured payload. The
    §2.5 mechanism under test is IN: a per-instance channel shift is
    exactly the style carrier Eq. 4 strips, so a privatized stream must
    score at chance while the leaky control (IN off) must not.
  * MEMBERSHIP inference — client-level membership under non-iid data:
    decide whether a captured payload came from a client whose traffic
    the attacker observed before (each client carries a persistent
    latent signature — the per-client shift — so re-identifying the
    signature IS membership, the 1912.04977 framing for non-iid
    populations).

``advantage = accuracy - chance`` where chance is the majority-class
rate of the held-out split (the no-information baseline), so "at
chance" means advantage ≈ 0 regardless of class balance. Every report
is deterministic in the provided PRNG key. With a flight recorder
installed, each attack emits an ``attack`` event (scalar results only).
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import recorder as _obs

from .audit import evaluate_adversary, train_adversary
from .tap import PayloadTap, TapRecord


class AttackReport(NamedTuple):
    """One attack's scorecard on a held-out split."""
    attack: str           # "attribute" | "membership" | caller-chosen
    accuracy: float       # held-out attack accuracy
    chance: float         # majority-class rate of the held-out split
    advantage: float      # accuracy - chance (≈0 == the attack failed)
    conditional_entropy_bits: float   # Thm. 1 H(Y|Z) estimate
    n_train: int
    n_test: int
    n_classes: int


def _records(source: Union[PayloadTap, Sequence[TapRecord]]
             ) -> List[TapRecord]:
    recs = list(source.records if isinstance(source, PayloadTap)
                else source)
    if not recs:
        raise ValueError("no captured payloads to attack")
    return recs


def payload_histograms(payloads, n_atoms: int) -> np.ndarray:
    """Captured payloads -> (N_samples, n_atoms) code-usage histograms.

    Each payload unpacks to (C, B, T[, S]) indices; every (client,
    sample) row becomes one normalized histogram over the transmitted
    alphabet. Works unchanged for GSVQ streams (alphabet = n_groups,
    n_slices codes per position) — the attacker needs only the alphabet
    size, which is wire metadata (``bits``).
    """
    rows = []
    for p in payloads:
        idx = np.asarray(p.unpack())
        flat = idx.reshape(idx.shape[0] * idx.shape[1], -1)
        onehot = flat[..., None] == np.arange(n_atoms)[None, None, :]
        rows.append(onehot.sum(axis=1) / flat.shape[1])
    return np.concatenate(rows, axis=0).astype(np.float32)


def sample_labels(records: Sequence[TapRecord], key: str) -> np.ndarray:
    """Per-SAMPLE int labels from per-record tap meta: a record's meta
    value may be a scalar (all its samples share it — the per-client
    case) or an array of one label per sample."""
    parts = []
    for r in records:
        n = int(r.payload.shape[0]) * int(r.payload.shape[1])
        v = r.meta.get(key)
        if v is None:
            raise KeyError(f"tap record lacks meta[{key!r}]")
        arr = np.asarray(v).reshape(-1)
        if arr.size == 1:
            arr = np.full((n,), int(arr[0]))
        if arr.size != n:
            raise ValueError(f"meta[{key!r}] has {arr.size} labels for "
                             f"{n} samples")
        parts.append(arr.astype(np.int32))
    return np.concatenate(parts, axis=0)


def shadow_attack(key, features, labels, n_classes: int, *,
                  attack: str = "attribute", steps: int = 200,
                  train_frac: float = 0.8,
                  test_features=None, test_labels=None) -> AttackReport:
    """Train the Thm. 1 probe as a shadow classifier and score it.

    Default: permute with ``key`` and split ``train_frac``/rest (the
    audit idiom — captured streams arrive client-sorted). Passing
    ``test_features``/``test_labels`` overrides the split with a
    disjoint evaluation capture (the membership setting, where train and
    test come from different rounds).
    """
    feats = jnp.asarray(features)
    y = jnp.asarray(labels).astype(jnp.int32)
    kp, kt = jax.random.split(key)
    if test_features is None:
        n = int(y.shape[0])
        perm = jax.random.permutation(kp, n)
        feats, y = feats[perm], y[perm]
        split = int(train_frac * n)
        tr_f, tr_y = feats[:split], y[:split]
        te_f, te_y = feats[split:], y[split:]
    else:
        tr_f, tr_y = feats, y
        te_f = jnp.asarray(test_features)
        te_y = jnp.asarray(test_labels).astype(jnp.int32)
    params = train_adversary(kt, tr_f, tr_y, n_classes, steps=steps)
    m = evaluate_adversary(params, te_f, te_y, n_classes)
    counts = np.bincount(np.asarray(te_y), minlength=n_classes)
    chance = float(counts.max() / max(1, counts.sum()))
    report = AttackReport(
        attack=attack, accuracy=m.accuracy, chance=chance,
        advantage=m.accuracy - chance,
        conditional_entropy_bits=m.conditional_entropy_bits,
        n_train=int(tr_y.shape[0]), n_test=int(te_y.shape[0]),
        n_classes=int(n_classes))
    rec = _obs.active()
    if rec is not None:
        rec.event("attack", attack=report.attack,
                  accuracy=report.accuracy, chance=report.chance,
                  advantage=report.advantage,
                  n_train=report.n_train, n_test=report.n_test,
                  n_classes=report.n_classes)
    return report


def attribute_inference(key, source: Union[PayloadTap, Sequence[TapRecord]],
                        *, attribute: str, n_classes: int, n_atoms: int,
                        steps: int = 200) -> AttackReport:
    """Predict a sensitive per-sample attribute from captured payloads."""
    recs = _records(source)
    feats = payload_histograms([r.payload for r in recs], n_atoms)
    y = sample_labels(recs, attribute)
    return shadow_attack(key, feats, y, n_classes,
                         attack=f"attribute:{attribute}", steps=steps)


def membership_inference(key,
                         train: Union[PayloadTap, Sequence[TapRecord]],
                         test: Union[PayloadTap, Sequence[TapRecord]], *,
                         n_atoms: int, flag: str = "member",
                         steps: int = 200) -> AttackReport:
    """Decide whether a captured payload's client was previously
    observed. ``train`` is the attacker's shadow capture (its own
    member/non-member ground truth in ``meta[flag]``); ``test`` is a
    later, disjoint capture of the same population plus fresh clients.
    """
    tr = _records(train)
    te = _records(test)
    tr_f = payload_histograms([r.payload for r in tr], n_atoms)
    te_f = payload_histograms([r.payload for r in te], n_atoms)
    return shadow_attack(key, tr_f, sample_labels(tr, flag), 2,
                         attack="membership", steps=steps,
                         test_features=te_f,
                         test_labels=sample_labels(te, flag))
