"""Batched multi-client simulation engine (OCTOPUS §2.2 at population scale).

``core.octopus`` models ONE client's transition functions. Serving the
ROADMAP's "heavy traffic from millions of users" needs the whole client
population to advance per device call, so this engine:

  * stacks ``ClientState`` pytrees along a leading client axis
    (``replicate_clients`` / ``stack_clients``),
  * runs the Steps 2-3 front half (fine-tune + the round's SINGLE
    encoder pass) for every client in ONE jitted ``jax.vmap`` call —
    hundreds of clients per dispatch instead of a Python loop,
  * optionally wraps the vmap in ``shard_map`` over the mesh 'data' axis
    so client shards advance on separate devices (the same mesh contract
    as repro.distributed.sharding),
  * finishes Steps 3-5 in ONE fused quantize-pack-stats dispatch
    (repro.kernels.encode_codes): every client's latents are matched
    against that client's OWN codebook, bit-packed into a per-client
    dense uint32 record stream, and reduced to the EMA statistics that
    complete the Step 5 refresh — the population's (N, K) distance
    matrix and int32 index tensor never exist, and the per-round uplink
    bytes are MEASURED from the buffers that would actually cross the
    network, per-client padding included (§2.8).

Step 2's deployment of a population is ONE compiled dispatch too
(``SimEngine.init_clients``): every leaf of the fresh ``ClientState`` is
broadcast to ``(n_clients, ...)`` in one program, and on a mesh lands
already sharded over 'data', where the round reads it.

Typical use::

    eng = SimEngine(cfg, lr=1e-4, gamma=0.99)
    clients = eng.init_clients(server, n_clients=256)   # one dispatch
    clients, packed = eng.round(clients, data)     # data: (C, B, ...)
    server = eng.merge_into_server(server, clients)   # Step 5 tail
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import octopus as OC
from repro.core.dvqae import DVQAEConfig
from repro.wire.payload import CodePayload, normalize_labels


def __getattr__(name):
    if name == "PackedCodes":
        raise ImportError(
            "sim.engine.PackedCodes was removed; use "
            "repro.wire.CodePayload (same carrier, versioned wire format)")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# ----------------------------------------------------------- client batches

def replicate_clients(server: OC.ServerState, n_clients: int
                      ) -> OC.ClientState:
    """Step 2 deployment for a population: one ClientState pytree whose
    leaves carry a leading (n_clients, ...) axis.

    The plain definition, one op per leaf when called eagerly;
    ``SimEngine.init_clients`` runs it as one compiled program."""
    client = OC.client_init(server)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_clients,) + x.shape), client)


def stack_clients(clients) -> OC.ClientState:
    """List of per-client states -> one stacked ClientState pytree."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *clients)


def unstack_clients(batch: OC.ClientState):
    """Stacked ClientState -> list of per-client states (debug/interop)."""
    n = client_batch_size(batch)
    return [jax.tree.map(lambda x: x[i], batch) for i in range(n)]


def client_batch_size(batch: OC.ClientState) -> int:
    return int(jax.tree.leaves(batch)[0].shape[0])


# ------------------------------------------------------------------ engine

class SimEngine:
    """Compiles one population round (Steps 2-5) and reuses it.

    mesh=None        — single host: plain jitted vmap.
    mesh=Mesh(...)   — shard_map over the mesh 'data' axis: the client
                       axis is sharded, each device group advances its
                       slice of the population (n_clients must divide by
                       the data-axis size).
    """

    def __init__(self, cfg: DVQAEConfig, *, lr: float = 1e-4,
                 gamma: float = 0.99, n_local_steps: int = 1,
                 mesh=None):
        self.cfg = cfg
        self.bits = OC.transmit_bits(cfg)
        self.mesh = mesh

        def one_client(client, batch):
            return OC.client_round(client, cfg, batch, lr=lr, gamma=gamma,
                                   n_local_steps=n_local_steps)

        def one_client_encode(client, batch):
            """Steps 2-3 front half (the same code path client_round
            runs), latents flattened to (P, M) for the fused dispatch."""
            client, z = OC.client_finetune_encode(
                client, cfg, batch, lr=lr, n_local_steps=n_local_steps)
            return client, z.reshape(-1, z.shape[-1])

        step = jax.vmap(one_client)
        bits = self.bits

        def _round(clients, data):
            """One vmapped encode + ONE fused quantize-pack-stats dispatch
            for the (per-shard) population: the kernel quantizes every
            client's latents against that client's own codebook, emits
            each client's packed uplink record, and hands back the
            per-client EMA statistics that complete Step 5 without a
            second network pass."""
            from repro.core.ema import ema_update_from_stats
            from repro.kernels.ops import encode_codes
            clients, z = jax.vmap(one_client_encode)(clients, data)
            payload, counts, sums = encode_codes(
                z, clients.params["codebook"], bits=bits,
                n_groups=cfg.n_groups, n_slices=cfg.n_slices)
            ema = ema_update_from_stats(clients.ema, counts, sums,
                                        gamma=gamma)
            params = {**clients.params, "codebook": ema.codebook}
            clients = OC.ClientState(params=params, ema=ema,
                                     step=clients.step)
            return clients, payload

        def _deploy(params, n_clients):
            # the deploy reads only the server's params, never its AdamW
            # state, so only they cross into the program
            return replicate_clients(
                OC.ServerState(params=params, opt=None, step=None),
                n_clients)

        round_fn = _round
        deploy_kw = {}
        if mesh is not None:
            from jax.sharding import Mesh, NamedSharding
            from jax.sharding import PartitionSpec as P
            spec = P("data")
            # fresh clients land sharded over 'data', as the round reads
            # them: no reshard before each cohort's round
            deploy_kw["out_shardings"] = NamedSharding(mesh, spec)
            step = jax.shard_map(step, mesh=mesh, in_specs=(spec, spec),
                                 out_specs=(spec, spec), check_vma=False)
            # the WHOLE round — encode, fused dispatch, EMA — runs inside
            # the shard-mapped body, so the kernel sees only its shard's
            # clients; per-shard payloads are per-client record streams,
            # so concatenating them along rows IS the population payload
            round_fn = jax.shard_map(_round, mesh=mesh,
                                     in_specs=(spec, spec),
                                     out_specs=(spec, spec), check_vma=False)
            self._uplink_sharding = NamedSharding(
                Mesh(mesh.devices.flat[:1], ("uplink",)), P())
        self._step = step
        self._step_jit = jax.jit(step)
        self._round = jax.jit(round_fn)
        self._deploy = jax.jit(_deploy, static_argnums=1, **deploy_kw)
        self._shape_cache = {}

    # ------------------------------------------------------------- rounds

    def init_clients(self, server: OC.ServerState, n_clients: int
                     ) -> OC.ClientState:
        """Step 2: ``n_clients`` fresh clients deployed from ``server``,
        bit-identical to ``replicate_clients`` but in ONE compiled
        dispatch (one program per ``n_clients``); on a mesh every leaf
        comes out sharded over 'data'."""
        return self._deploy(server.params, int(n_clients))

    def round(self, clients: OC.ClientState, data, *, version: int = 0,
              labels=None) -> Tuple[OC.ClientState, CodePayload]:
        """Advance every client one full round (Steps 2-5).

        data: (C, B, ...) — one local batch per client, client axis
        matching the stacked state. Returns the new population state and
        the round's wire payload: one per-client record stream per
        client (``n_records == C``), straight from the fused encode
        kernel — the population's int32 index tensor never exists.

        ``version`` stamps the codebook version the codes were packed
        under; ``labels`` (per-task dict or bare (C, B) array) ride the
        payload into the server's CodeStore.
        """
        c = client_batch_size(clients)
        assert data.shape[0] == c, (data.shape, c)
        idx_shape = self._index_shape(clients, data)
        clients, payload = self._round(clients, data)
        if self.mesh is not None:
            # the uplink leaves the client shards: gather the per-shard
            # record streams onto the mesh's first device, where the
            # server's unpack / decode kernels run (a pallas_call takes no
            # sharded operand). A one-device mesh, because a plain
            # device_put keeps an Explicit mesh axis in the array's type.
            payload = jax.device_put(payload, self._uplink_sharding)
        return clients, CodePayload(
            payload=payload, bits=self.bits, shape=idx_shape, n_records=c,
            version=int(version),
            labels=normalize_labels(labels, c * int(data.shape[1])),
            privatized=True)

    def round_indices(self, clients: OC.ClientState, data
                      ) -> Tuple[OC.ClientState, jax.Array]:
        """Steps 2-5 for the (sub)population, returning the UNPACKED int32
        code indices (C, B, T[, n_c]).

        The async code server (repro.server) uses this instead of
        ``round`` because participants split into delivery groups —
        stragglers, drops, per-version lanes — and each group packs its
        own uplink buffer; one population-wide payload would glue them
        together.
        """
        c = client_batch_size(clients)
        assert data.shape[0] == c, (data.shape, c)
        return self._step_jit(clients, data)

    def _index_shape(self, clients, data) -> Tuple[int, ...]:
        cache_key = tuple(data.shape)
        if cache_key not in self._shape_cache:
            out = jax.eval_shape(lambda c, d: self._step(c, d)[1],
                                 clients, data)
            self._shape_cache[cache_key] = tuple(out.shape)
        return self._shape_cache[cache_key]

    # ------------------------------------------------------- server side

    def merge_into_server(self, server: OC.ServerState,
                          clients: OC.ClientState) -> OC.ServerState:
        """Step 5 tail: count-weighted merge of the population's synced
        codebooks into the global dictionary — one einsum, no loop."""
        return OC.server_merge_codebooks(server, clients.params["codebook"],
                                         clients.ema.counts)

    def dequantize(self, server: OC.ServerState, packed: CodePayload):
        """Step 6 entry: fused decode of a round's payload against the
        CURRENT global codebook — the packed word stream goes straight to
        feature rows (ops.decode_codes); the int32 index tensor is never
        materialised."""
        feats = OC.codes_to_features(server, self.cfg, packed)
        return feats.reshape((-1,) + feats.shape[2:])   # merge client axis
