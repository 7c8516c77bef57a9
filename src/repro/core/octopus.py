"""The OCTOPUS distributed learning protocol (§2.2, Steps 1-6).

Server:  Step 1  train initial global DVQ-AE on public data (ATD)
Clients: Step 2  one-shot local fine-tune (encoder + joint decoder)
         Step 3  disentangle; only Z• (indices) are releasable
         Step 4  transmit code indices at high frequency
         Step 5  low-frequency codebook EMA refresh -> sync to server
Server:  Step 6  train downstream tasks on gathered codes

The implementation is functional: ``ClientState`` / ``ServerState`` pytrees
plus pure transition functions, so the whole protocol jits and the client
population maps onto the mesh 'data' axis (one client shard per device
group) — see repro.distributed for the sharded variant.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.optim.adamw import AdamWState, adamw_init, adamw_update
from .dvqae import DVQAEConfig, DVQAEOut, forward, init_dvqae
from . import ema as ema_mod
from .ema import (EMAState, assignment_stats, ema_update_from_stats,
                  init_ema)


class ClientState(NamedTuple):
    params: dict              # local DVQ-AE (encoder fine-tuned, decoder joint)
    ema: EMAState             # local codebook EMA accumulator
    step: jax.Array


class ServerState(NamedTuple):
    params: dict              # global DVQ-AE
    opt: AdamWState
    step: jax.Array


class Transmission(NamedTuple):
    """LEGACY carrier: what crossed the network before the unified wire
    protocol. New code speaks ``repro.wire.CodePayload`` — the single
    versioned carrier — via the ``repro.wire`` session facades; packed
    Transmissions are coerced with ``repro.wire.as_payload``.

    ``payload`` is the dense ceil(log2 K)-bit packed word stream (see
    repro.kernels.pack_bits) — the bytes that would actually hit the
    uplink; ``nbytes`` is MEASURED from it (via ``CodePayload.nbytes``,
    the repo's one byte accounting), not computed from a formula.
    ``indices`` keeps the unpacked int32 view for local convenience.
    """
    indices: jax.Array        # int32 code matrix (B, T[, n_c])
    nbytes: int               # measured size of the packed payload
    labels: Optional[jax.Array] = None
    payload: Optional[jax.Array] = None   # (n_groups, W) uint32 bit-stream
    bits: int = 0             # bits per transmitted code


def transmit_bits(cfg: DVQAEConfig) -> int:
    """Bits per transmitted code index (§2.8: 5-10 bits in the paper).

    With GSVQ (any ``n_groups``/``n_slices`` > 1) clients transmit one
    *group* index per slice per position, so the per-code alphabet is
    n_groups — including sliced configs with n_groups == 1, whose codes
    are a single-symbol alphabet (1-bit floor), NOT K. Per position this
    is ``n_slices * transmit_bits == gsvq_bits_per_position``; the
    measured payload size (``repro.wire.CodePayload.nbytes``, the single
    §2.8 accounting) follows.
    """
    from repro.kernels.pack_bits import code_bits
    if cfg.n_groups > 1 or cfg.n_slices > 1:
        return code_bits(cfg.n_groups)
    return code_bits(cfg.codebook_size)


# --------------------------------------------------------------- Step 1

def server_init(key, cfg: DVQAEConfig, lr: float = 1e-3) -> ServerState:
    params = init_dvqae(key, cfg)
    return ServerState(params=params, opt=adamw_init(params),
                       step=jnp.zeros((), jnp.int32))


def server_pretrain_step(state: ServerState, cfg: DVQAEConfig, batch,
                         lr: float = 1e-3, group_axis=None
                         ) -> Tuple[ServerState, DVQAEOut]:
    """One ATD pretraining step of the global DVQ-AE (Step 1)."""
    def loss_fn(p):
        out = forward(p, cfg, batch, group_axis=group_axis)
        return out.loss, out

    grads, out = jax.grad(loss_fn, has_aux=True)(state.params)
    params, opt = adamw_update(state.params, grads, state.opt, lr=lr)
    return ServerState(params=params, opt=opt, step=state.step + 1), out


def server_pretrain(key, server: ServerState, cfg: DVQAEConfig, x, *,
                    steps: int, batch: int = 32, lr: float = 1e-3
                    ) -> Tuple[ServerState, Optional[DVQAEOut]]:
    """Step 1 driver: ``steps`` ATD pretraining steps over random
    minibatches of ``x``. Returns (server, last step's DVQAEOut — None
    when steps == 0). Shared by the launch entries and benchmarks so the
    fold_in/randint minibatch idiom lives in one place.
    """
    out = None
    for i in range(steps):
        sel = jax.random.randint(jax.random.fold_in(key, i), (batch,), 0,
                                 x.shape[0])
        server, out = server_pretrain_step(server, cfg, x[sel], lr=lr)
    return server, out


# --------------------------------------------------------------- Step 2

def client_init(server: ServerState) -> ClientState:
    """Deploy the global model to a client; codebook starts frozen."""
    return ClientState(params=jax.tree.map(lambda x: x, server.params),
                       ema=init_ema(server.params["codebook"]),
                       step=jnp.zeros((), jnp.int32))


def client_finetune_step(client: ClientState, cfg: DVQAEConfig, batch,
                         lr: float = 1e-4, opt: Optional[AdamWState] = None,
                         ) -> Tuple[ClientState, AdamWState, DVQAEOut]:
    """One-shot fine-tuning: encoder + decoder update, codebook FROZEN
    (§2.6 'initially, the codebook is frozen for local fine-tuning')."""
    if opt is None:
        opt = adamw_init({"encoder": client.params["encoder"],
                          "decoder": client.params["decoder"]})

    def loss_fn(enc_dec):
        p = {**enc_dec, "codebook": client.params["codebook"]}
        out = forward(p, cfg, batch)
        return out.loss, out

    trainable = {"encoder": client.params["encoder"],
                 "decoder": client.params["decoder"]}
    grads, out = jax.grad(loss_fn, has_aux=True)(trainable)
    new, opt = adamw_update(trainable, grads, opt, lr=lr)
    params = {**new, "codebook": client.params["codebook"]}
    return (ClientState(params=params, ema=client.ema, step=client.step + 1),
            opt, out)


# ----------------------------------------------------------- Steps 3 + 4
# (client_transmit / unpack_transmission are RETIRED — see _TOMBSTONES
# at the end of the module; the uplink is repro.wire.CodePayload now)


# --------------------------------------------------------------- Step 5

def client_encode(params, cfg: DVQAEConfig, batch):
    """ONE encoder pass into quantizer space: (z, spatial).

    z is IN(z_e) when the disentanglement layer is on — the space the
    quantizer matches in and the space EMA atoms must move in (EMA toward
    raw latents drags atoms off the normalized manifold). Every Step 3-5
    consumer (quantize, pack, refresh statistics) feeds off this single
    pass; see :func:`client_round`.
    """
    from .disentangle import instance_norm_latent
    from .dvqae import encode
    z_e, spatial = encode(params, cfg, batch)
    if cfg.apply_in:
        z_e = instance_norm_latent(z_e)
    return z_e, spatial


def quantize_indices(cfg: DVQAEConfig, z, codebook):
    """Transmitted codes of quantizer-space latents z (..., M):
    (...,) atom ids for plain VQ, (..., n_c) per-slice group indices for
    GSVQ — identical to ``forward(...).latent.indices`` without the
    decoder/loss work."""
    from .gsvq import gsvq_indices
    from .vq import kernel_nearest_atom
    if cfg.n_groups > 1 or cfg.n_slices > 1:
        return gsvq_indices(z, codebook, n_groups=cfg.n_groups,
                            n_slices=cfg.n_slices)
    return kernel_nearest_atom(z, codebook)


def refresh_stats(cfg: DVQAEConfig, z, indices):
    """Eq. 7-8 sufficient statistics (counts (K,), sums (K, M)) of one
    batch — the jnp twin of the fused encode kernel's stats outputs.

    GSVQ: indices is a (..., n_c) per-slice GROUP-index matrix, not flat
    atom ids — every slice's group index lands on its group's
    representative atom (group centre) and votes its position's FULL
    latent into that atom's EMA mass. (Feeding the raw matrix to the
    segment sum scattered onto wrong atoms; n_groups == 1 sliced configs
    used to skip the mapping entirely.)
    """
    if cfg.n_groups > 1 or cfg.n_slices > 1:
        ng = cfg.codebook_size // cfg.n_groups
        indices = indices * ng + ng // 2               # (..., n_c) atom ids
        z = jnp.broadcast_to(z[..., None, :], indices.shape + z.shape[-1:])
    return assignment_stats(z, indices, cfg.codebook_size)


def client_codebook_refresh(client: ClientState, cfg: DVQAEConfig, batch,
                            gamma: float = 0.99, *, stats=None
                            ) -> ClientState:
    """Low-frequency EMA refresh of the local codebook (Eq. 9).

    ``stats``: precomputed (counts, sums) — e.g. straight from the fused
    encode kernel (kernels/encode_codes.py) or :func:`refresh_stats` —
    in which case ``batch`` is ignored and NO network pass runs. Without
    it, one encoder pass derives the statistics (this entry used to run
    the full ``forward`` AND a second encode for the same refresh).
    """
    if stats is None:
        z, _ = client_encode(client.params, cfg, batch)
        idx = quantize_indices(cfg, z, client.params["codebook"])
        stats = refresh_stats(cfg, z, idx)
    ema = ema_update_from_stats(client.ema, *stats, gamma=gamma)
    params = {**client.params, "codebook": ema.codebook}
    return ClientState(params=params, ema=ema, step=client.step)


def server_merge_codebooks(server: ServerState,
                           client_codebooks,
                           client_counts,
                           *, staleness=None,
                           staleness_decay: float = 1.0) -> ServerState:
    """Count-weighted average of synced client codebooks (global dictionary
    update, Step 5 tail). counts: per-atom EMA N_i of each client.

    Accepts either sequences of per-client (K, M) / (K,) arrays or the
    already-stacked (M_clients, K, M) / (M_clients, K) arrays the batched
    sim engine carries.

    ``staleness`` (optional, (M_clients,) int): how many codebook versions
    behind the global dictionary each client's sync is —
    ``repro.server.CodebookRegistry.merge`` discounts stale contributions by
    ``staleness_decay ** staleness`` on top of the count weights, so a
    client that slept through two merges pulls the dictionary less than
    one that synced last round.
    """
    cbs = jnp.asarray(client_codebooks) if isinstance(
        client_codebooks, jax.Array) else jnp.stack(list(client_codebooks))
    cts = jnp.asarray(client_counts) if isinstance(
        client_counts, jax.Array) else jnp.stack(list(client_counts))
    w = cts
    if staleness is not None:
        decay = staleness_decay ** jnp.asarray(staleness, jnp.float32)
        w = w * decay[:, None]
    tot = jnp.sum(w, axis=0)                                  # (K,)
    merged = jnp.einsum("ck,ckm->km",
                        w / jnp.maximum(tot[None], 1e-9), cbs)
    # atoms with no effective contribution (e.g. every client fully
    # staleness-decayed) keep the current dictionary instead of
    # collapsing to zero
    cur = server.params["codebook"].astype(merged.dtype)
    merged = jnp.where(tot[:, None] > 1e-9, merged, cur)
    params = {**server.params, "codebook": merged.astype(
        server.params["codebook"].dtype)}
    return ServerState(params=params, opt=server.opt, step=server.step)


def server_merge_stats(server: ServerState,
                       stats: "ema_mod.MergeStats") -> ServerState:
    """Step-5 tail from ASSOCIATIVE merge statistics (cohort streaming).

    ``stats`` is the int64 fixed-point accumulator from
    :func:`repro.core.ema.merge_stats` / ``merge_stats_add`` — the cohort
    engine folds each cohort's contribution in as it streams, and this
    finishes the merge once. Because the accumulation is exact integer
    addition, the resulting dictionary is bit-identical for ANY cohort
    partition or order of the same client set (see
    ``ema.merge_codebook``). Atoms with zero accumulated weight keep the
    current dictionary, matching :func:`server_merge_codebooks`.
    """
    merged = ema_mod.merge_codebook(stats, server.params["codebook"])
    params = {**server.params,
              "codebook": jnp.asarray(merged)}
    return ServerState(params=params, opt=server.opt, step=server.step)


# ------------------------------------------------------- Steps 2-5 (round)

def client_finetune_encode(client: ClientState, cfg: DVQAEConfig, batch, *,
                           lr: float = 1e-4, n_local_steps: int = 1
                           ) -> Tuple[ClientState, jax.Array]:
    """The round's Steps 2-3 front half, shared by every round variant
    (and the engine's vmapped body — bit-parity between the population
    round and the single-client loop rests on this being ONE code path):
    ``n_local_steps`` of frozen-codebook fine-tuning, then the round's
    SINGLE encoder pass into quantizer space."""
    opt = None
    for _ in range(n_local_steps):
        client, opt, _ = client_finetune_step(client, cfg, batch, lr=lr,
                                              opt=opt)
    z, _ = client_encode(client.params, cfg, batch)
    return client, z


def client_round(client: ClientState, cfg: DVQAEConfig, batch, *,
                 lr: float = 1e-4, gamma: float = 0.99,
                 n_local_steps: int = 1
                 ) -> Tuple[ClientState, jax.Array]:
    """One full client round: Steps 2-5 for a single client, as a pure
    jittable function of (state, batch).

    Runs ``n_local_steps`` of frozen-codebook fine-tuning (Step 2), then
    ONE encoder pass feeds everything downstream: the releasable code
    indices (Steps 3-4) and the Eq. 7-8 statistics behind the EMA
    codebook refresh (Step 5). (This used to re-run the network three
    times — forward for the indices, then forward AND encode again
    inside the refresh — for the same latents.)

    Returns (new_client, int32 indices). This unfused jnp path is the
    tests' reference for the program paths — ``SimEngine`` and
    ``repro.wire.OctopusClient.round``, both through
    :func:`client_uplink` — which never materialize the index tensor
    and ship a ``CodePayload``.
    """
    client, z = client_finetune_encode(client, cfg, batch, lr=lr,
                                       n_local_steps=n_local_steps)
    idx = quantize_indices(cfg, z, client.params["codebook"])
    client = client_codebook_refresh(client, cfg, batch, gamma=gamma,
                                     stats=refresh_stats(cfg, z, idx))
    return client, idx


def client_uplink(clients: ClientState, cfg: DVQAEConfig, z, *,
                  gamma: float = 0.99, refresh: bool = True
                  ) -> Tuple[ClientState, jax.Array]:
    """The fused Steps 3-5 tail of C >= 1 client records.

    ``clients`` carries a leading (C, ...) axis; ``z`` is (C, P, M),
    each record's quantizer-space latents. ONE ``ops.encode_codes``
    dispatch matches every record against its own (K, M) codebook,
    bit-packs its codes into its own uint32 record stream and reduces
    the Eq. 7-8 statistics, from which the Step 5 EMA refresh follows
    (skipped with ``refresh=False``). Neither the (N, K) distance matrix
    nor the int32 index tensor exists. Returns (clients, (C, W) words).
    """
    from repro.kernels.ops import encode_codes
    words, counts, sums = encode_codes(
        z, clients.params["codebook"], bits=transmit_bits(cfg),
        n_groups=cfg.n_groups, n_slices=cfg.n_slices)
    if refresh:
        clients = client_codebook_refresh(clients, cfg, None, gamma=gamma,
                                          stats=(counts, sums))
    return clients, words


# --------------------------------------------------------------- Step 6

def gather_codes(transmissions: Sequence[Transmission], *,
                 fill_label: int = -1):
    """Server-side dataset assembly from client uploads.

    Labels: if every upload carries them they concatenate; if none do,
    ``labels`` is None. MIXED labeled/unlabeled uploads keep sample
    alignment with the gathered codes by filling the unlabeled uploads'
    slots with ``fill_label`` (default -1) — semi-supervised Step 6
    training masks those out. (Keying off ``transmissions[0]`` used to
    crash on [labeled, unlabeled] and silently drop [unlabeled, labeled].)
    """
    idx = jnp.concatenate([t.indices for t in transmissions], axis=0)
    have = [t.labels is not None for t in transmissions]
    if not any(have):
        labels = None
    elif all(have):
        labels = jnp.concatenate([jnp.asarray(t.labels)
                                  for t in transmissions], axis=0)
    else:
        ref = jnp.asarray(next(t.labels for t in transmissions
                               if t.labels is not None))
        dtype = ref.dtype
        if jnp.issubdtype(dtype, jnp.unsignedinteger):
            dtype = jnp.int32           # fill_label must stay negative
        labels = jnp.concatenate(
            [jnp.asarray(t.labels).astype(dtype) if t.labels is not None
             else jnp.full((int(t.indices.shape[0]),) + ref.shape[1:],
                           fill_label, dtype)
             for t in transmissions], axis=0)
    total_bytes = sum(t.nbytes for t in transmissions)
    return idx, labels, total_bytes


def decode_table(cfg: DVQAEConfig, codebook):
    """Decode-side lookup table for the fused kernel: ((rows, F), n_slices).

    Plain VQ: the codebook itself ((K, M), 1) — a code gathers its atom.
    GSVQ: the stacked per-slice group-mean table
    ((n_slices * n_groups, m), n_slices) — gathering row ``s*n_groups+g``
    is mathematically identical to ``gsvq_dequantize_indices``'s uniform
    group average (kernels/decode_codes.py consumes this layout).
    """
    from .gsvq import gsvq_group_mean_table
    if cfg.n_groups > 1 or cfg.n_slices > 1:
        t = gsvq_group_mean_table(codebook, n_groups=cfg.n_groups,
                                  n_slices=cfg.n_slices)
        return t.reshape(cfg.n_slices * cfg.n_groups, -1), cfg.n_slices
    return codebook, 1


def codes_to_features(server: Optional[ServerState], cfg: DVQAEConfig,
                      indices, *, codebook=None):
    """Dequantize gathered codes into downstream-task features.

    ``indices`` is either an int32 code array OR a packed carrier — a
    ``repro.wire.CodePayload`` (or a legacy packed ``Transmission``,
    coerced via ``repro.wire.as_payload``). The
    carrier takes the fused decode path (repro.wire.codec, ONE
    ops.decode_codes dispatch): straight from the uint32 word stream to
    feature rows, never materialising the index or gathered-atom
    tensors. Both paths agree bit-exactly for VQ and to fp32 tolerance
    for GSVQ means.

    ``codebook`` overrides the server's current dictionary — the versioned
    code store (repro.server) passes the registry snapshot the codes were
    packed under, so Step 5 lag never decodes against the wrong table.
    """
    from .gsvq import gsvq_dequantize_indices
    from .vq import dequantize
    if codebook is None:
        if server is None:
            raise ValueError("codes_to_features needs a ServerState or an "
                             "explicit codebook= to decode against")
        codebook = server.params["codebook"]
    cb = codebook
    from repro.wire.codec import decode_payloads
    from repro.wire.payload import as_payload
    p = as_payload(indices)
    if p is not None:
        return decode_payloads([p], cfg, cb)[0]
    if isinstance(indices, Transmission):       # unpacked legacy carrier
        indices = indices.indices
    if cfg.n_groups > 1 or cfg.n_slices > 1:
        return gsvq_dequantize_indices(indices, cb, n_groups=cfg.n_groups,
                                       n_slices=cfg.n_slices)
    return dequantize(indices, cb)


# ------------------------------------------------------------ tombstones
# The PR-5 wire shims finished their deprecation cycle: importing one now
# raises with a pointer at the unified wire layer, the same retirement
# pattern as repro.sim's IngestBuffer/PackedCodes.

_TOMBSTONES = {
    "client_transmit": "repro.wire.OctopusClient.transmit / .round "
                       "(CodePayload uplink)",
    "client_round_fused": "repro.wire.OctopusClient.round / "
                          "repro.wire.round_words",
    "unpack_transmission": "repro.wire.CodePayload.unpack (via "
                           "repro.wire.as_payload for legacy "
                           "Transmissions)",
}


def __getattr__(name):
    if name in _TOMBSTONES:
        raise ImportError(
            f"repro.core.octopus.{name} was removed; use "
            f"{_TOMBSTONES[name]} — the unified wire carrier, see "
            f"repro.wire")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
