"""Composable decoder stack: blocks assembled from LayerSpecs, scanned
over segment repeat axes (small HLO for 512-device dry-runs), with a
unified decode-cache protocol across attention/Mamba/xLSTM mixers.
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention, layers, mamba, moe, xlstm
from repro.models.config import LayerSpec, ModelConfig
from repro.models.layers import rmsnorm, rmsnorm_init

Params = Any
Cache = Any


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def block_init(key: jax.Array, cfg: ModelConfig, spec: LayerSpec) -> Params:
    k_mix, k_ffn = jax.random.split(key)
    p: dict = {"norm1": rmsnorm_init(cfg.d_model)}
    if spec.mixer == "attn":
        p["mixer"] = (attention.mla_init(k_mix, cfg)
                      if cfg.attn_kind == "mla"
                      else attention.gqa_init(k_mix, cfg))
    elif spec.mixer == "mamba":
        p["mixer"] = mamba.mamba_init(k_mix, cfg)
    elif spec.mixer == "mlstm":
        p["mixer"] = xlstm.mlstm_init(k_mix, cfg)
    elif spec.mixer == "slstm":
        p["mixer"] = xlstm.slstm_init(k_mix, cfg)
    else:
        raise ValueError(spec.mixer)
    if spec.ffn == "dense":
        p["norm2"] = rmsnorm_init(cfg.d_model)
        p["ffn"] = layers.mlp_init(k_ffn, cfg.d_model, cfg.d_ff)
    elif spec.ffn == "moe":
        p["norm2"] = rmsnorm_init(cfg.d_model)
        p["ffn"] = moe.moe_init(k_ffn, cfg)
    return p


def block_apply(p: Params, x: jnp.ndarray, positions: jnp.ndarray,
                cfg: ModelConfig, spec: LayerSpec, window: int = 0
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    h = rmsnorm(x, p["norm1"], cfg.norm_eps)
    if spec.mixer == "attn":
        apply = (attention.mla_apply if cfg.attn_kind == "mla"
                 else attention.gqa_apply)
        h = apply(p["mixer"], h, positions, cfg, window=window)
    elif spec.mixer == "mamba":
        h = mamba.mamba_apply(p["mixer"], h, cfg)
    elif spec.mixer == "mlstm":
        h = xlstm.mlstm_apply(p["mixer"], h, cfg)
    elif spec.mixer == "slstm":
        h = xlstm.slstm_apply(p["mixer"], h, cfg)
    x = x + h
    aux = jnp.zeros((), jnp.float32)
    if spec.ffn == "dense":
        x = x + layers.mlp_apply(p["ffn"], rmsnorm(x, p["norm2"],
                                                   cfg.norm_eps))
    elif spec.ffn == "moe":
        y, aux = moe.moe_apply(p["ffn"], rmsnorm(x, p["norm2"],
                                                 cfg.norm_eps), cfg)
        x = x + y
    return x, aux


def block_init_cache(cfg: ModelConfig, spec: LayerSpec, batch: int,
                     max_len: int, window: int = 0,
                     quantized: bool | None = None) -> Cache:
    if quantized is None:
        import os
        quantized = os.environ.get("REPRO_QUANT_KV") == "1"
    if spec.mixer == "attn":
        if cfg.attn_kind == "mla":
            return attention.mla_init_cache(cfg, batch, max_len, window)
        return attention.gqa_init_cache(cfg, batch, max_len, window,
                                        quantized=quantized)
    if spec.mixer == "mamba":
        return mamba.mamba_init_cache(cfg, batch)
    if spec.mixer == "mlstm":
        return xlstm.mlstm_init_cache(cfg, batch)
    if spec.mixer == "slstm":
        return xlstm.slstm_init_cache(cfg, batch)
    raise ValueError(spec.mixer)


def block_decode(p: Params, x: jnp.ndarray, cache: Cache, cfg: ModelConfig,
                 spec: LayerSpec, window: int = 0
                 ) -> tuple[jnp.ndarray, Cache]:
    h = rmsnorm(x, p["norm1"], cfg.norm_eps)
    if spec.mixer == "attn":
        if cfg.attn_kind == "mla":
            h, cache = attention.mla_decode(p["mixer"], h, cache, cfg)
        else:
            h, cache = attention.gqa_decode(p["mixer"], h, cache, cfg,
                                            window=window)
    elif spec.mixer == "mamba":
        h, cache = mamba.mamba_decode(p["mixer"], h, cache, cfg)
    elif spec.mixer == "mlstm":
        h, cache = xlstm.mlstm_decode(p["mixer"], h, cache, cfg)
    elif spec.mixer == "slstm":
        h, cache = xlstm.slstm_decode(p["mixer"], h, cache, cfg)
    x = x + h
    if spec.ffn == "dense":
        x = x + layers.mlp_apply(p["ffn"], rmsnorm(x, p["norm2"],
                                                   cfg.norm_eps))
    elif spec.ffn == "moe":
        y, _ = moe.moe_apply(p["ffn"], rmsnorm(x, p["norm2"], cfg.norm_eps),
                             cfg)
        x = x + y
    return x, cache


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------

def init(key: jax.Array, cfg: ModelConfig) -> Params:
    k_emb, k_head, k_seg = jax.random.split(key, 3)
    params: dict = {
        "embed": layers.embed_init(k_emb, cfg.padded_vocab, cfg.d_model),
        "final_norm": rmsnorm_init(cfg.d_model),
        "segments": [],
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = layers.dense_init(k_head, cfg.d_model,
                                              cfg.padded_vocab)
    for si, (repeat, pattern) in enumerate(cfg.segments):
        k_si = jax.random.fold_in(k_seg, si)
        pat_params = []
        for pi, spec in enumerate(pattern):
            ks = jax.random.split(jax.random.fold_in(k_si, pi), repeat)
            pat_params.append(
                jax.vmap(lambda k, s=spec: block_init(k, cfg, s))(ks))
        params["segments"].append(tuple(pat_params))
    return params


def _constrain_logits(logits: jnp.ndarray) -> jnp.ndarray:
    """Pin logits to (batch over FSDP axes) × (vocab over model).

    Without this SPMD sometimes materializes the *full-batch* logits per
    device at the unembed/loss boundary (§Perf: 2×12.9 GB/device/step
    measured on granite-moe train_4k)."""
    mesh = jax.sharding.get_abstract_mesh()
    names = mesh.axis_names
    if "model" not in names:
        return logits
    from jax.sharding import PartitionSpec as P
    fsdp = tuple(a for a in ("pod", "data") if a in names)
    total = 1
    for a in fsdp:
        total *= mesh.shape[a]
    b = (fsdp if len(fsdp) > 1 else fsdp[0]) \
        if fsdp and logits.shape[0] % max(total, 1) == 0 else None
    v = "model" if logits.shape[-1] % mesh.shape["model"] == 0 else None
    spec = P(b, None, v) if logits.ndim == 3 else P(b, v)
    return jax.lax.with_sharding_constraint(logits, spec)


def _constrain_batch_only(x: jnp.ndarray) -> jnp.ndarray:
    """Pin (B, T, d) activations to batch-over-FSDP, d replicated — stops
    SPMD from resharding the unembed input to a d-over-data layout whose
    contraction partial-sums all-reduce the full-batch logits."""
    mesh = jax.sharding.get_abstract_mesh()
    names = mesh.axis_names
    if "model" not in names:
        return x
    from jax.sharding import PartitionSpec as P
    fsdp = tuple(a for a in ("pod", "data") if a in names)
    total = 1
    for a in fsdp:
        total *= mesh.shape[a]
    if not fsdp or x.shape[0] % total != 0:
        return x
    b = fsdp if len(fsdp) > 1 else fsdp[0]
    return jax.lax.with_sharding_constraint(x, P(b, None, None))


def _logits(params: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = layers.unembed(params["embed"], x, transpose=True)
    else:
        logits = layers.unembed(params["lm_head"], x, transpose=False)
    logits = _constrain_logits(logits)
    if cfg.padded_vocab != cfg.vocab:
        # mask pad columns so loss/argmax never see them
        pad = jnp.arange(cfg.padded_vocab) >= cfg.vocab
        logits = jnp.where(pad, -1e30, logits)
    return logits


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray | None = None,
            embeds: jnp.ndarray | None = None,
            positions: jnp.ndarray | None = None, window: int = 0,
            remat: bool = True, return_hidden: bool = False
            ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Train/prefill forward → (logits (B,T,V) f32, aux loss scalar);
    ``return_hidden=True`` skips the unembed and returns the final
    hidden states instead (used by the sharded-CE loss path)."""
    if embeds is None:
        embeds = layers.embed_apply(params["embed"], tokens)
    x = embeds
    B, T, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    aux = jnp.zeros((), jnp.float32)

    for seg_params, (repeat, pattern) in zip(params["segments"],
                                             cfg.segments):
        def seg_body(carry, lp, pattern=pattern):
            xc, auxc = carry
            for spec, p in zip(pattern, lp):
                xc, a = block_apply(p, xc, positions, cfg, spec,
                                    window=window)
                auxc = auxc + a
            return (xc, auxc), None

        body = jax.checkpoint(seg_body) if remat else seg_body
        (x, aux), _ = jax.lax.scan(body, (x, aux), seg_params)
    if return_hidden:
        return x, aux
    return _logits(params, x, cfg), aux


def _sharded_ce(params: Params, x: jnp.ndarray, labels: jnp.ndarray,
                cfg: ModelConfig) -> jnp.ndarray | None:
    """Manual-SPMD unembed + cross entropy via shard_map (§Perf).

    The auto-partitioned unembed/CE pair kept resharding the full-batch
    logits (2×12.9 GB/device/step on granite-moe even after constraint
    pinning).  Here each (data, model) shard computes its local
    (B_loc, T, V_loc) logits block and only (B, T)-sized pmax/psum cross
    shards ever move.  Returns None when inapplicable (no mesh / tied
    embeddings / non-dividing shapes) — caller falls back to the
    auto-sharded path.
    """
    mesh = jax.sharding.get_abstract_mesh()
    names = mesh.axis_names
    if "model" not in names or cfg.tie_embeddings \
            or "lm_head" not in params:
        return None
    from jax.sharding import PartitionSpec as P
    fsdp = tuple(a for a in ("pod", "data") if a in names)
    total = 1
    for a in fsdp:
        total *= mesh.shape[a]
    V = cfg.padded_vocab
    msize = mesh.shape["model"]
    if not fsdp or x.shape[0] % total != 0 or V % msize != 0:
        return None
    b = fsdp if len(fsdp) > 1 else fsdp[0]
    B, T, _ = x.shape

    def f(xl, nl, wl, ll):
        xl = rmsnorm(xl, nl, cfg.norm_eps).astype(jnp.float32)
        logits = xl @ wl.astype(jnp.float32)           # (B_loc, T, V_loc)
        Vl = logits.shape[-1]
        col = jax.lax.axis_index("model") * Vl + jnp.arange(Vl)
        logits = jnp.where(col >= cfg.vocab, -1e30, logits)
        m = jax.lax.pmax(jax.lax.stop_gradient(logits.max(-1)), "model")
        ssum = jax.lax.psum(jnp.exp(logits - m[..., None]).sum(-1), "model")
        lse = jnp.log(ssum) + m
        oh = ll[..., None] == col
        lt = jax.lax.psum(jnp.where(oh, logits, 0.0).sum(-1), "model")
        ce = jnp.sum(lse - lt)
        for a in (b if isinstance(b, tuple) else (b,)):
            ce = jax.lax.psum(ce, a)
        return ce

    ce_sum = jax.shard_map(
        f, mesh=mesh,
        in_specs=(P(b, None, None), P(None), P(None, "model"), P(b, None)),
        out_specs=P())(x, params["final_norm"], params["lm_head"], labels)
    return ce_sum / (B * T)


def _ce_from_logits(logits: jnp.ndarray, labels: jnp.ndarray,
                    valid: jnp.ndarray | None = None) -> jnp.ndarray:
    m = jax.lax.stop_gradient(logits.max(axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[..., 0]
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    onehot = _constrain_logits(onehot)
    ce = lse - jnp.sum(onehot * logits, axis=-1)
    if valid is not None:
        valid = jnp.broadcast_to(valid, ce.shape)
        return (ce * valid).sum() / jnp.maximum(valid.sum(), 1.0)
    return ce.mean()


def mtp_loss(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
             labels: jnp.ndarray, depth: int = 1,
             weight: float = 0.3) -> jnp.ndarray:
    """Multi-token-prediction auxiliary objective (DeepSeek-V3 §2.2).

    Simplification recorded in DESIGN.md: V3 uses one extra transformer
    block per MTP depth; here the *same* trunk/head predicts the
    (1+depth)-ahead token from each position — the sequential-prediction
    training signal without a second tower.  Positions whose target falls
    off the sequence are masked out.
    """
    logits, _ = forward(params, cfg, tokens=tokens)
    shifted = jnp.roll(labels, -depth, axis=1)
    T = labels.shape[1]
    valid = (jnp.arange(T) < T - depth).astype(logits.dtype)[None, :]
    return weight * _ce_from_logits(logits, shifted, valid)


def lm_loss(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            labels: jnp.ndarray, window: int = 0
            ) -> tuple[jnp.ndarray, dict]:
    import os
    # Opt-in (§Perf iteration, REFUTED as a default): the shard_map CE pins
    # its input to P(data, None, None), and that constraint propagates back
    # into the layer-scan carry — every layer then reshards (52 GB/device
    # all-gathers).  Kept for meshes where the carry is already batch-only.
    if os.environ.get("REPRO_SHARDED_CE") == "1":
        hidden, aux = forward(params, cfg, tokens=tokens, window=window,
                              return_hidden=True)
        ce = _sharded_ce(params, hidden, labels, cfg)
        if ce is not None:
            return ce + aux, {"ce": ce, "aux": aux}
        # fall through: no mesh / inapplicable
        logits = _logits(params, hidden, cfg)
    else:
        logits, aux = forward(params, cfg, tokens=tokens, window=window)
    # Sharding-friendly CE: `take_along_axis` across a vocab-sharded logits
    # tensor makes SPMD all-gather the full (B, T, V/shard) activations
    # (§Perf: measured 2×12.9 GB/device/step on granite-moe).  The
    # one-hot contraction + logsumexp form keeps every vocab reduction
    # local with only (B, T)-sized cross-shard psums.
    m = jax.lax.stop_gradient(logits.max(axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[..., 0]
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    onehot = _constrain_logits(onehot)   # co-shard with logits
    label_logit = jnp.sum(onehot * logits, axis=-1)
    ce = (lse - label_logit).mean()
    return ce + aux, {"ce": ce, "aux": aux}


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               window: int = 0, quantized: bool | None = None) -> list:
    caches = []
    for repeat, pattern in cfg.segments:
        pat = []
        for spec in pattern:
            c = block_init_cache(cfg, spec, batch, max_len, window,
                                 quantized)
            pat.append(jax.tree.map(
                lambda a: jnp.zeros((repeat,) + a.shape, a.dtype), c))
        caches.append(tuple(pat))
    return caches


def decode_step(params: Params, cfg: ModelConfig, token: jnp.ndarray,
                caches: list, window: int = 0
                ) -> tuple[jnp.ndarray, list]:
    """token: (B, 1) int32 → (logits (B, 1, V), updated caches)."""
    x = layers.embed_apply(params["embed"], token)
    new_caches = []
    for seg_params, seg_cache, (repeat, pattern) in zip(
            params["segments"], caches, cfg.segments):
        def seg_body(xc, lp_lc, pattern=pattern):
            lp, lc = lp_lc
            new_lc = []
            for spec, p, c in zip(pattern, lp, lc):
                xc, cn = block_decode(p, xc, c, cfg, spec, window=window)
                new_lc.append(cn)
            return xc, tuple(new_lc)

        x, nc = jax.lax.scan(seg_body, x, (seg_params, seg_cache))
        new_caches.append(nc)
    return _logits(params, x, cfg), new_caches
