"""Mixture-of-Experts: top-k router + sort-based capacity dispatch.

Dispatch strategy (TPU-adapted, GShard-capacity semantics without the
O(tokens x experts x capacity) one-hot):

  1. top-k routing -> (token, expert) assignment list of length N*k,
  2. position-in-expert via a single argsort over expert ids (O(Nk log Nk)
     instead of an (Nk, E) cumsum tensor),
  3. scatter tokens into a dense (E, C, d) buffer (capacity-dropped),
  4. batched expert matmul via einsum over the leading expert axis — this is
     the axis sharded over 'model' (expert parallelism); XLA SPMD turns the
     scatter/gather into the all-to-all,
  5. gather back and combine with gate weights.

Aux losses: switch-style load-balance + router z-loss.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .layers import act_fn, dense_init


class MoEOut(NamedTuple):
    y: jax.Array
    aux_loss: jax.Array


def init_moe(key, cfg, dtype=jnp.float32):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    p = {"router": dense_init(ks[0], d, m.n_experts, jnp.float32)}
    def expert_stack(k, d_in, d_out):
        return jax.random.uniform(
            k, (m.n_experts, d_in, d_out), dtype,
            -1.0 / jnp.sqrt(d_in), 1.0 / jnp.sqrt(d_in))
    p["experts"] = {
        "wi": expert_stack(ks[1], d, m.d_ff_expert),
        "wg": expert_stack(ks[2], d, m.d_ff_expert),
        "wo": expert_stack(ks[3], m.d_ff_expert, d),
    }
    if m.n_shared_experts:
        ff_sh = m.n_shared_experts * m.d_ff_expert
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "wi": dense_init(k1, d, ff_sh, dtype),
            "wg": dense_init(k2, d, ff_sh, dtype),
            "wo": dense_init(k3, ff_sh, d, dtype),
        }
    return p


def router_topk(logits, k, scoring="softmax"):
    """logits (N, E) fp32 -> (gate (N,k), idx (N,k), probs (N,E))."""
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        gate, idx = jax.lax.top_k(scores, k)
        gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)
        probs = scores / jnp.maximum(jnp.sum(scores, -1, keepdims=True), 1e-9)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = jax.lax.top_k(probs, k)
        gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)
    return gate, idx, probs


def load_balance_loss(probs, idx, n_experts):
    """Switch-Transformer aux: E * sum_e f_e * P_e."""
    N, k = idx.shape
    # fraction of assignments to each expert (counts over N*k)
    counts = jnp.zeros((n_experts,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    f = counts / (N * k)
    P = jnp.mean(probs, axis=0)
    return n_experts * jnp.sum(f * P)


def positions_in_expert(expert_ids, n_experts):
    """Rank of each assignment within its expert, via one argsort.

    expert_ids: (A,) int32. Returns (A,) int32 positions.
    """
    A = expert_ids.shape[0]
    order = jnp.argsort(expert_ids)                    # stable
    sorted_ids = expert_ids[order]
    counts = jnp.zeros((n_experts,), jnp.int32).at[expert_ids].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1]])
    pos_sorted = jnp.arange(A, dtype=jnp.int32) - starts[sorted_ids]
    return jnp.zeros((A,), jnp.int32).at[order].set(pos_sorted)


def _moe_shardmap(params, cfg, x, mesh, dp_axes, activation) -> MoEOut:
    """Expert-parallel MoE via shard_map (§Perf iteration 2c).

    Key observation: the residual stream is sharded over the data axes and
    REPLICATED over 'model', while experts are sharded over 'model'. So no
    token ever needs to move: each model shard routes its (replicated)
    token block, keeps only assignments to its own E/TP experts, runs the
    expert matmuls locally, and the combine is ONE psum of (tokens, d)
    partial outputs over 'model'. Collective cost per layer = the psum
    (~tokens x d), versus the full dispatch-buffer all-reduce XLA emits
    for the scatter formulation (measured 18.8-37.6 GB/op on DeepSeek).
    """
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    B, T, d = x.shape
    tp = mesh.shape["model"]
    e_loc = m.n_experts // tp
    a = act_fn(activation)
    k = m.n_experts_per_tok

    def body(xb, router, wi, wg, wo):
        # xb: (B_loc, T, d) — this dp shard's tokens (same for all model j)
        n = xb.shape[0] * xb.shape[1]
        xf = xb.reshape(n, d)
        logits = (xf.astype(jnp.float32) @ router).astype(jnp.float32)
        gate, idx, probs = router_topk(logits, k, m.router_scoring)
        aux = m.router_aux_coef * load_balance_loss(probs, idx, m.n_experts)
        aux = aux + 1e-3 * jnp.mean(
            jnp.square(jax.nn.logsumexp(logits, axis=-1)))
        aux = jax.lax.pmean(aux, dp_axes)

        j = jax.lax.axis_index("model")
        e_lo = j * e_loc
        A = n * k
        expert_ids = idx.reshape(A)
        gates = gate.reshape(A)
        token_ids = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
        local_e = expert_ids - e_lo
        mine = (local_e >= 0) & (local_e < e_loc)
        if xb.shape[1] == 1:
            C = A
        else:
            C = max(k, int(round(A * m.capacity_factor / m.n_experts)))
        seg = jnp.where(mine, local_e, e_loc)        # e_loc = discard bucket
        pos = positions_in_expert(seg, e_loc + 1)
        keep = mine & (pos < C)
        slot = jnp.where(keep, seg * C + pos, e_loc * C)
        updates = xf[token_ids] * keep[:, None].astype(xf.dtype)
        buf = jnp.zeros((e_loc * C + 1, d), xf.dtype).at[slot].add(updates)
        bufe = buf[: e_loc * C].reshape(e_loc, C, d)
        h = a(jnp.einsum("ecd,edf->ecf", bufe, wi)) * jnp.einsum(
            "ecd,edf->ecf", bufe, wg)
        out_buf = jnp.einsum("ecf,efd->ecd", h, wo).reshape(e_loc * C, d)
        out_buf = jnp.concatenate(
            [out_buf, jnp.zeros((1, d), out_buf.dtype)])
        gathered = out_buf[slot] * (gates * keep).astype(xf.dtype)[:, None]
        y = jnp.sum(gathered.reshape(n, k, d), axis=1)
        y = jax.lax.psum(y, "model")                 # combine across experts
        return y.reshape(xb.shape), aux

    e = params["experts"]
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp_axes, None, None), P(), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(P(dp_axes, None, None), P()),
        check_vma=False)
    y, aux = fn(x, params["router"], e["wi"], e["wg"], e["wo"])

    if "shared" in params:
        s = params["shared"]
        xf = x.reshape(-1, d)
        from repro import hints
        hdn = hints.ffn_hidden((a(xf @ s["wi"]) * (xf @ s["wg"])
                                ).reshape(B, T, -1)).reshape(B * T, -1)
        y = y + (hdn @ s["wo"]).reshape(B, T, d)
    return MoEOut(y=y, aux_loss=aux)


def moe_apply(params, cfg, x, *, activation="silu") -> MoEOut:
    """x: (B, T, d) -> (B, T, d), aux_loss scalar.

    Two dispatch layouts (cfg.moe.dispatch):
      * "flat"     — (E*C, d) buffer, E on 'model'. Simple; under SPMD the
        token->buffer scatter lowers to replicate+all-reduce of the whole
        buffer (expensive at DeepSeek scale).
      * "bucketed" — (S, E, C_loc, d) buffer with a leading source-data-
        shard dim. Tokens are contiguous per dp shard, so each shard's
        scatter is local; the dp->model exchange moves only real token
        payloads (all-to-all-sized). §Perf iteration 2b.
    """
    m = cfg.moe
    B, T, d = x.shape
    N = B * T
    xf = x.reshape(N, d)
    a = act_fn(activation)

    logits = (xf.astype(jnp.float32) @ params["router"]).astype(jnp.float32)
    gate, idx, probs = router_topk(logits, m.n_experts_per_tok, m.router_scoring)
    aux = m.router_aux_coef * load_balance_loss(probs, idx, m.n_experts)
    aux = aux + 1e-3 * jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))

    k = m.n_experts_per_tok
    A = N * k
    expert_ids = idx.reshape(A)
    gates = gate.reshape(A)
    token_ids = jnp.repeat(jnp.arange(N, dtype=jnp.int32), k)

    from repro import hints
    e = params["experts"]

    st = hints._state()
    if m.dispatch == "shardmap" and st is not None and T > 1:
        # T == 1 (decode) stays on the flat path: the shard_map in_specs
        # re-gather FSDP'd expert weights EVERY step, which dwarfs the
        # one-token dispatch it saves (measured 2.7x collective regression
        # on deepseek decode_32k).
        mesh, dp_axes = st
        tp = mesh.shape.get("model", 1)
        dpsz = hints.dp_size()
        if (tp > 1 and m.n_experts % tp == 0 and B % dpsz == 0):
            return _moe_shardmap(params, cfg, x, mesh, dp_axes, activation)

    if m.dispatch == "bucketed" and hints.dp_size() > 1 \
            and N % hints.dp_size() == 0:
        S = hints.dp_size()
        n_loc = N // S                       # tokens per data shard
        C = max(1, int(round(A * m.capacity_factor / (m.n_experts * S))))
        shard_of = token_ids // n_loc        # (A,) source shard
        # rank within the (shard, expert) segment
        seg = shard_of * m.n_experts + expert_ids
        pos = positions_in_expert(seg, S * m.n_experts)
        keep = pos < C
        slot = jnp.where(keep, seg * C + pos, 0)
        updates = xf[token_ids] * keep[:, None].astype(xf.dtype)
        buf = jnp.zeros((S * m.n_experts * C, d), xf.dtype
                        ).at[slot].add(updates)
        buf = hints.expert_buffer_bucketed(
            buf.reshape(S, m.n_experts, C, d))
        # expert-major view: the (S@data -> E@model) transpose is the a2a
        bufe = hints.expert_buffer(
            buf.transpose(1, 0, 2, 3).reshape(m.n_experts, S * C, d))
        h = a(jnp.einsum("ecd,edf->ecf", bufe, e["wi"])) * jnp.einsum(
            "ecd,edf->ecf", bufe, e["wg"])
        out_e = jnp.einsum("ecf,efd->ecd", h, e["wo"])
        out_buf = hints.expert_buffer_bucketed(
            out_e.reshape(m.n_experts, S, C, d).transpose(1, 0, 2, 3)
        ).reshape(S * m.n_experts * C, d)
    else:
        # floor at top-k so tiny batches keep all first choices; decode
        # (T == 1) runs DROPLESS so single-token outputs match the
        # teacher-forced path exactly (capacity drops are a train-time
        # throughput trade, not a serving semantic)
        if T == 1:
            C = A
        else:
            C = max(k, int(round(A * m.capacity_factor / m.n_experts)))
        pos = positions_in_expert(expert_ids, m.n_experts)
        keep = pos < C
        slot = jnp.where(keep, expert_ids * C + pos, 0)
        # dispatch: scatter token features into (E*C, d) expert buffers
        updates = xf[token_ids] * keep[:, None].astype(xf.dtype)
        buf = jnp.zeros((m.n_experts * C, d), xf.dtype).at[slot].add(updates)
        buf = hints.expert_buffer(buf.reshape(m.n_experts, C, d))
        # batched expert matmuls (expert axis -> 'model' sharding)
        h = a(jnp.einsum("ecd,edf->ecf", buf, e["wi"])) * jnp.einsum(
            "ecd,edf->ecf", buf, e["wg"])
        out_buf = jnp.einsum("ecf,efd->ecd", h,
                             e["wo"]).reshape(m.n_experts * C, d)

    # combine: gather back, gate, sum over k slots per token
    gathered = out_buf[slot] * (gates * keep).astype(xf.dtype)[:, None]
    y = jnp.sum(gathered.reshape(N, k, d), axis=1)

    if "shared" in params:
        s = params["shared"]
        y = y + (a(xf @ s["wi"]) * (xf @ s["wg"])) @ s["wo"]
    return MoEOut(y=y.reshape(B, T, d), aux_loss=aux)
