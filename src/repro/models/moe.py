"""Mixture-of-Experts layer: top-k router + capacity-based dispatch.

Dispatch strategy (default ``impl="capacity"``): tokens·top_k slots are
sorted by expert id and scattered into a fixed `(E, capacity)` buffer
(overflow drops, standard GShard/Switch semantics).  Expert FFNs then run
as *batched dense* einsums over the buffer — exact FLOPs in
`cost_analysis`, MXU-shaped matmuls on TPU, and the expert axis shards
cleanly (expert parallelism on the `model` mesh axis; the token→buffer
scatter lowers to the all-to-all the paper's aggregation-routing story
maps onto).

``impl="ragged"`` routes through `jax.lax.ragged_dot` (MegaBlocks-style
grouped matmul, no drops) — preferred on real TPUs with Mosaic support;
kept out of the dry-run because XLA:CPU's cost model bills ragged_dot as
E dense matmuls, which would corrupt the roofline's compute term.

DeepSeek-V3 details supported: shared (always-on) experts beside the
routed ones, sigmoid routing option, aux-free bias — we implement the
standard softmax router with a Switch-style load-balance aux loss
(coefficient per config).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.models.config import ModelConfig
from repro.models.layers import dense_init


def _constrain_ep(buf: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """§Perf knob (REPRO_SHARD_MOE=1): pin the dispatch buffer to
    expert-parallel sharding so the token→expert movement lowers as one
    all-to-all instead of whatever resharding chain SPMD picks."""
    import os
    if os.environ.get("REPRO_SHARD_MOE") != "1" \
            or cfg.moe.sharding != "ep":
        return buf
    from jax.sharding import PartitionSpec as P
    mesh = jax.sharding.get_abstract_mesh()
    if "model" not in mesh.axis_names:
        return buf
    if cfg.moe.n_experts % mesh.shape["model"] != 0:
        return buf
    if buf.ndim == 4:   # per-row dispatch: (B, E, cap, d)
        return jax.lax.with_sharding_constraint(
            buf, P(None, "model", None, None))
    return jax.lax.with_sharding_constraint(buf, P("model", None, None))


def moe_init(key: jax.Array, cfg: ModelConfig) -> dict:
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    p = {
        "router": (jax.random.normal(ks[0], (d, m.n_experts), jnp.float32)
                   * d ** -0.5).astype(jnp.float32),
        "gate": jax.random.normal(ks[1], (m.n_experts, d, m.d_expert),
                                  jnp.float32).astype(layers.PARAM_DTYPE)
        * d ** -0.5,
        "up": jax.random.normal(ks[2], (m.n_experts, d, m.d_expert),
                                jnp.float32).astype(layers.PARAM_DTYPE)
        * d ** -0.5,
        "down": jax.random.normal(ks[3], (m.n_experts, m.d_expert, d),
                                  jnp.float32).astype(layers.PARAM_DTYPE)
        * m.d_expert ** -0.5,
    }
    if m.n_shared:
        p["shared"] = layers.mlp_init(ks[4], d, m.n_shared * m.d_expert)
    return p


def _route(params: dict, xf: jnp.ndarray, cfg: ModelConfig):
    """xf: (S, d) → (topk weights (S,k), ids (S,k), aux loss)."""
    m = cfg.moe
    logits = xf.astype(jnp.float32) @ params["router"]        # (S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, m.top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)       # renormalize
    # Switch-style load balance: E · Σ_e f_e · P_e
    me = probs.mean(0)                                         # (E,)
    ce = jnp.zeros((m.n_experts,)).at[ids.reshape(-1)].add(
        1.0 / ids.size)
    aux = m.n_experts * jnp.sum(me * ce) * m.router_aux_coef
    return w, ids, aux


def _expert_ffn(params: dict, buf: jnp.ndarray) -> jnp.ndarray:
    """buf: (E, cap, d) → (E, cap, d) batched dense SwiGLU."""
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, params["gate"]))
    h = g * jnp.einsum("ecd,edf->ecf", buf, params["up"])
    return jnp.einsum("ecf,efd->ecd", h, params["down"])


def moe_apply(params: dict, x: jnp.ndarray, cfg: ModelConfig,
              capacity_factor: float = 1.25,
              impl: str = "capacity") -> tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, T, d) → (y (B, T, d), aux loss scalar).

    ``impl="capacity"`` (default) dispatches *per batch row*: each row
    sorts its own T·k slots into a (E, cap_row) buffer, so under a
    batch-sharded mesh the sort/scatter stays device-local and the only
    cross-device movement is the expert einsum's all-to-all/all-gather
    (§Perf iteration: the earlier global-sort formulation lowered to a
    distributed 8M-element sort — hundreds of GB of collective traffic
    per MoE layer).  ``impl="capacity_global"`` keeps the global-sort
    form for comparison; ``impl="ragged"`` is the MegaBlocks-style path.
    """
    m = cfg.moe
    B, T, d = x.shape
    S = B * T
    xf = x.reshape(S, d)
    w, ids, aux = _route(params, xf, cfg)

    if impl == "capacity":
        y = _dispatch_per_row(params, x, w.reshape(B, T, m.top_k),
                              ids.reshape(B, T, m.top_k), cfg,
                              capacity_factor)
        if m.n_shared:
            y = y + layers.mlp_apply(params["shared"], xf).reshape(B, T, d)
        return y.astype(x.dtype), aux

    k = m.top_k
    flat_ids = ids.reshape(-1)                                 # (S·k,)
    order = jnp.argsort(flat_ids, stable=True)
    sorted_ids = flat_ids[order]
    tok_of_slot = order // k                                   # source token

    if impl == "ragged":
        counts = jnp.zeros((m.n_experts,), jnp.int32).at[sorted_ids].add(1)
        xs = xf[tok_of_slot]                                   # (S·k, d)
        g = jax.nn.silu(jax.lax.ragged_dot(xs, params["gate"], counts))
        h = g * jax.lax.ragged_dot(xs, params["up"], counts)
        ys = jax.lax.ragged_dot(h, params["down"], counts)     # (S·k, d)
        y = jnp.zeros((S, d), jnp.float32).at[tok_of_slot].add(
            ys.astype(jnp.float32) * w.reshape(-1)[order][:, None])
    else:
        cap = max(int(S * k * capacity_factor / m.n_experts), 1)
        cap = -(-cap // 8) * 8                                  # align
        counts = jnp.zeros((m.n_experts,), jnp.int32).at[sorted_ids].add(1)
        starts = jnp.cumsum(counts) - counts                    # exclusive
        pos_in_e = jnp.arange(S * k) - starts[sorted_ids]
        keep = pos_in_e < cap
        dest = jnp.where(keep, sorted_ids * cap + pos_in_e, m.n_experts * cap)
        buf = jnp.zeros((m.n_experts * cap, d), x.dtype)
        buf = buf.at[dest].set(xf[tok_of_slot], mode="drop")
        buf = _constrain_ep(buf.reshape(m.n_experts, cap, d), cfg)
        out_buf = _constrain_ep(_expert_ffn(params, buf), cfg)
        ys = out_buf.reshape(-1, d).at[dest].get(
            mode="fill", fill_value=0.0)                        # (S·k, d)
        y = jnp.zeros((S, d), jnp.float32).at[tok_of_slot].add(
            ys.astype(jnp.float32)
            * (w.reshape(-1)[order] * keep)[:, None])

    if m.n_shared:
        y = y + layers.mlp_apply(params["shared"], xf)
    return y.reshape(B, T, d).astype(x.dtype), aux


def _dispatch_per_row(params: dict, x: jnp.ndarray, w: jnp.ndarray,
                      ids: jnp.ndarray, cfg: ModelConfig,
                      capacity_factor: float) -> jnp.ndarray:
    """Row-local capacity dispatch.  x: (B,T,d); w/ids: (B,T,k)."""
    m = cfg.moe
    B, T, d = x.shape
    k = m.top_k
    cap = max(int(T * k * capacity_factor / m.n_experts), 1)
    cap = -(-cap // 4) * 4

    flat_ids = ids.reshape(B, T * k)
    order = jnp.argsort(flat_ids, axis=-1, stable=True)        # (B, T·k)
    sorted_ids = jnp.take_along_axis(flat_ids, order, axis=-1)
    tok_of_slot = order // k
    counts = jax.nn.one_hot(sorted_ids, m.n_experts,
                            dtype=jnp.int32).cumsum(axis=1)
    # position within expert group = rank among equal ids seen so far − 1
    pos_in_e = jnp.take_along_axis(
        counts, sorted_ids[..., None], axis=-1)[..., 0] - 1    # (B, T·k)
    keep = pos_in_e < cap
    dest = jnp.where(keep, sorted_ids * cap + pos_in_e,
                     m.n_experts * cap)

    xs = jnp.take_along_axis(
        x, tok_of_slot[..., None], axis=1)                     # (B,T·k,d)
    buf = jnp.zeros((B, m.n_experts * cap, d), x.dtype)
    bidx = jnp.arange(B)[:, None]
    buf = buf.at[bidx, dest].set(xs, mode="drop")
    buf = _constrain_ep(buf.reshape(B, m.n_experts, cap, d), cfg)

    g = jax.nn.silu(jnp.einsum("becd,edf->becf", buf, params["gate"]))
    h = g * jnp.einsum("becd,edf->becf", buf, params["up"])
    out = jnp.einsum("becf,efd->becd", h, params["down"])
    out = out.reshape(B, m.n_experts * cap, d)

    ys = out.at[bidx, dest].get(mode="fill", fill_value=0.0)   # (B,T·k,d)
    wk = jnp.take_along_axis(w.reshape(B, T * k), order, axis=-1) * keep
    y = jnp.zeros((B, T, d), jnp.float32)
    y = y.at[bidx, tok_of_slot].add(ys.astype(jnp.float32) * wk[..., None])
    return y


def moe_apply_dense_ref(params: dict, x: jnp.ndarray, cfg: ModelConfig
                        ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """O(E) dense oracle (every expert on every token) for unit tests."""
    m = cfg.moe
    B, T, d = x.shape
    xf = x.reshape(-1, d)
    w, ids, aux = _route(params, xf, cfg)
    g = jax.nn.silu(jnp.einsum("sd,edf->sef", xf, params["gate"]))
    h = g * jnp.einsum("sd,edf->sef", xf, params["up"])
    ye = jnp.einsum("sef,efd->sed", h, params["down"])         # (S, E, d)
    mask = jax.nn.one_hot(ids, m.n_experts)                    # (S, k, E)
    comb = jnp.einsum("sk,ske->se", w, mask)
    y = jnp.einsum("se,sed->sd", comb, ye)
    if m.n_shared:
        y = y + layers.mlp_apply(params["shared"], xf)
    return y.reshape(B, T, d).astype(x.dtype), aux
