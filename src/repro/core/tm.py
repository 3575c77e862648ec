"""Vectorized multiclass (weighted) Tsetlin Machine in pure JAX.

This is the client model of TPFL (paper §4.1, Fig. 1, Eq. 1).

Design notes
------------
* All state lives in two integer tensors so the whole machine `vmap`s over
  a population of federated clients and `jit`s end to end:

    - ``ta_state``  (C, m, 2o) int32  — Tsetlin Automaton states in [1, 2N];
      a literal is *included* in a clause iff state > N.
    - ``weights``   (C, m)     int32  — per-clause integer vote weights
      (weighted TM; set ``weighted=False`` for the classic unit-weight TM).

* Clause polarity is positional (paper §4.1): even-indexed clauses are
  positive (vote for the class), odd-indexed are negative.

* Training follows the canonical Type I / Type II feedback of Granmo's TM,
  sample-sequential via ``lax.scan`` (the paper trains clients sample by
  sample).  All stochastic choices use explicit `jax.random` keys.

* The clause-evaluation hot loop is factored through
  :mod:`repro.kernels.ops` so the Pallas TPU kernel and the pure-jnp oracle
  are interchangeable (``use_kernel`` flag).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class TMConfig:
    """Hyperparameters, named as in the paper (Table 2)."""

    n_classes: int = 10
    n_clauses: int = 300          # m, per class
    n_features: int = 784        # o (booleanized input bits)
    n_states: int = 127          # N; TA states span [1, 2N]
    s: float = 10.0              # sensitivity (specificity)
    T: int = 1000                # feedback / vote-clip threshold
    weighted: bool = True        # integer-weighted clauses (Eq. 1 weights)
    boost_true_positive: bool = False
    use_kernel: bool = False     # route clause eval through the Pallas kernel

    @property
    def n_literals(self) -> int:
        return 2 * self.n_features


class TMParams(NamedTuple):
    ta_state: jnp.ndarray  # (C, m, 2o) int32
    weights: jnp.ndarray   # (C, m) int32


def init_params(cfg: TMConfig, key: jax.Array) -> TMParams:
    """TA states start at the exclude/include boundary (N or N+1, random)."""
    shape = (cfg.n_classes, cfg.n_clauses, cfg.n_literals)
    coin = jax.random.bernoulli(key, 0.5, shape)
    ta = jnp.where(coin, cfg.n_states, cfg.n_states + 1).astype(jnp.int32)
    w = jnp.ones((cfg.n_classes, cfg.n_clauses), dtype=jnp.int32)
    return TMParams(ta_state=ta, weights=w)


def literals(x: jnp.ndarray) -> jnp.ndarray:
    """L = [x1..xo, ¬x1..¬xo]  (paper §4.1).  x is a boolean/0-1 array."""
    x = x.astype(jnp.int32)
    return jnp.concatenate([x, 1 - x], axis=-1)


def include_mask(params: TMParams, cfg: TMConfig) -> jnp.ndarray:
    return (params.ta_state > cfg.n_states).astype(jnp.int32)


def clause_polarity(cfg: TMConfig) -> jnp.ndarray:
    """+1 for even-indexed clauses, -1 for odd-indexed (paper §4.1)."""
    j = jnp.arange(cfg.n_clauses)
    return jnp.where(j % 2 == 0, 1, -1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Forward pass
# ---------------------------------------------------------------------------

def _clause_outputs_jnp(include: jnp.ndarray, lits: jnp.ndarray,
                        predict: bool) -> jnp.ndarray:
    """Conjunctive clause outputs.

    include: (C, m, 2o) int32, lits: (B, 2o) int32 → (B, C, m) int32.

    A clause fires iff no included literal is 0 in the input.  Empty clauses
    (nothing included) output 1 during learning, 0 during inference — the
    standard TM convention.
    """
    C, m, L = include.shape
    inc2 = include.reshape(C * m, L)
    # violations[b, cm] = #(included literals that are 0)
    viol = (1 - lits).astype(jnp.int32) @ inc2.T.astype(jnp.int32)
    fired = (viol == 0).astype(jnp.int32).reshape(lits.shape[0], C, m)
    if predict:
        nonempty = (inc2.sum(-1) > 0).astype(jnp.int32).reshape(1, C, m)
        fired = fired * nonempty
    return fired


def clause_outputs(params: TMParams, lits: jnp.ndarray, cfg: TMConfig,
                   predict: bool = False) -> jnp.ndarray:
    include = include_mask(params, cfg)
    if cfg.use_kernel:
        from repro.kernels import ops as kops
        return kops.clause_outputs(include, lits, predict=predict)
    return _clause_outputs_jnp(include, lits, predict)


def class_votes(params: TMParams, clauses: jnp.ndarray,
                cfg: TMConfig, clip: bool = True) -> jnp.ndarray:
    """Eq. 1: v[b, c] = Σ_j pol_j · w_j · clause_j, clipped to [-T, T]."""
    pol = clause_polarity(cfg)
    w = params.weights if cfg.weighted else jnp.ones_like(params.weights)
    v = jnp.einsum("bcm,cm->bc", clauses.astype(jnp.int32), (pol[None, :] * w))
    if clip:
        v = jnp.clip(v, -cfg.T, cfg.T)
    return v


def forward(params: TMParams, x: jnp.ndarray, cfg: TMConfig,
            predict: bool = False) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, o) 0/1 → (clause outputs (B,C,m), votes (B,C))."""
    lits = literals(x)
    cl = clause_outputs(params, lits, cfg, predict=predict)
    return cl, class_votes(params, cl, cfg)


def predict(params: TMParams, x: jnp.ndarray, cfg: TMConfig) -> jnp.ndarray:
    if cfg.use_kernel:
        from repro.kernels import ops as kops
        pol = clause_polarity(cfg)
        w = params.weights if cfg.weighted else jnp.ones_like(params.weights)
        votes = kops.fused_votes(include_mask(params, cfg), literals(x),
                                 (pol[None] * w), predict=True)
        # Eq. 1 clips votes to ±T before the argmax; under saturation the
        # clipped and raw argmax can disagree on ties, so the kernel path
        # must clip exactly like class_votes(..., clip=True) does.
        return jnp.argmax(jnp.clip(votes, -cfg.T, cfg.T), axis=-1)
    _, votes = forward(params, x, cfg, predict=True)
    return jnp.argmax(votes, axis=-1)


def accuracy(params: TMParams, x: jnp.ndarray, y: jnp.ndarray,
             cfg: TMConfig) -> jnp.ndarray:
    return (predict(params, x, cfg) == y).mean()


# ---------------------------------------------------------------------------
# Confidence (paper Alg. 1 step 6)
# ---------------------------------------------------------------------------

def confidence_scores(params: TMParams, x_conf: jnp.ndarray,
                      cfg: TMConfig, weighted: bool = False) -> jnp.ndarray:
    """conf[c] = Σ_{x∈D_conf} (Σ_j C⁺_j(x) − Σ_j C⁻_j(x)).

    Alg. 1 uses the *unweighted* clause-vote margin; set ``weighted=True``
    to use the Eq.-1 weighted margin instead (ablation knob).
    """
    lits = literals(x_conf)
    cl = clause_outputs(params, lits, cfg, predict=True)
    pol = clause_polarity(cfg)
    if weighted:
        pol = pol[None, :] * params.weights
        margin = jnp.einsum("bcm,cm->bc", cl, pol)
    else:
        margin = jnp.einsum("bcm,m->bc", cl, pol)
    return margin.sum(axis=0)  # (C,)


# ---------------------------------------------------------------------------
# Training: Type I / Type II feedback
# ---------------------------------------------------------------------------

def _feedback_one_class(ta: jnp.ndarray, w: jnp.ndarray, lits: jnp.ndarray,
                        clause_out: jnp.ndarray, votes: jnp.ndarray,
                        is_target: bool, key: jax.Array, cfg: TMConfig
                        ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Apply feedback to one class's clause bank for a single sample.

    ta: (m, 2o), w: (m,), lits: (2o,), clause_out: (m,), votes: scalar.
    For the target class, positive-polarity clauses receive Type I and
    negative-polarity Type II; for the sampled negative class it is the
    mirror image.
    """
    from repro.kernels import draws as kdraws
    m, L = ta.shape
    k_act, k_s1, k_s2 = jax.random.split(key, 3)

    # clause resampling: active w.p. (T ∓ v) / 2T.  Compared in the
    # integer domain against the host-built table of that f32 quotient
    # (same draws as bernoulli), so no backend divides on the device and
    # the Pallas epoch kernel makes the identical choice
    v = jnp.clip(votes, -cfg.T, cfg.T)
    thr = jnp.asarray(kdraws.activation_thresholds(cfg.T))[
        (cfg.T - v) if is_target else (cfg.T + v)]
    active = kdraws.act_bits(k_act, (m,)) < thr

    pol = clause_polarity(cfg)  # (m,)
    pos = pol > 0
    type1 = (pos if is_target else ~pos) & active      # (m,)
    type2 = ((~pos) if is_target else pos) & active

    # --- fused Type I / Type II TA transition -----------------------------
    # (Pallas kernel on TPU; jnp oracle otherwise — identical semantics,
    #  see repro/kernels/ref.py::ta_update_ref.)
    p_inc, p_dec = _feedback_probs(cfg)
    u_inc = jax.random.uniform(k_s1, (m, L))
    u_dec = jax.random.uniform(k_s2, (m, L))
    args = (ta, lits[None, :], clause_out[:, None],
            type1.astype(jnp.int32)[:, None], type2.astype(jnp.int32)[:, None],
            u_inc, u_dec)
    if cfg.use_kernel:
        from repro.kernels import ops as kops
        ta = kops.ta_update(*args, p_inc=p_inc, p_dec=p_dec,
                            n_states=cfg.n_states)
    else:
        from repro.kernels import ref as kref
        ta = kref.ta_update_ref(*args, p_inc=p_inc, p_dec=p_dec,
                                n_states=cfg.n_states)

    # --- weights (integer-weighted TM) -----------------------------------
    if cfg.weighted:
        winc = (type1 & clause_out.astype(bool)).astype(jnp.int32)
        wdec = (type2 & clause_out.astype(bool)).astype(jnp.int32)
        w = jnp.maximum(w + winc - wdec, 0)
    return ta, w


def _train_one_sample(params: TMParams, lits: jnp.ndarray, y: jnp.ndarray,
                      key: jax.Array, cfg: TMConfig) -> TMParams:
    """One sample's feedback, from its literal row ``lits`` (2o,)."""
    cl = clause_outputs(params, lits[None], cfg)   # (1, C, m)
    votes = class_votes(params, cl, cfg)           # (1, C)
    cl, votes = cl[0], votes[0]

    k_neg, k_t, k_n = jax.random.split(key, 3)
    # sample a negative class uniformly from the other C-1 classes
    offset = jax.random.randint(k_neg, (), 1, cfg.n_classes)
    ybar = (y + offset) % cfg.n_classes

    def upd(cls_idx, is_target, k):
        ta_c = params.ta_state[cls_idx]
        w_c = params.weights[cls_idx]
        return _feedback_one_class(ta_c, w_c, lits, cl[cls_idx],
                                   votes[cls_idx], is_target, k, cfg)

    ta_t, w_t = upd(y, True, k_t)
    ta = params.ta_state.at[y].set(ta_t)
    w = params.weights.at[y].set(w_t)
    ta_n, w_n = _feedback_one_class(ta[ybar], w[ybar], lits, cl[ybar],
                                    votes[ybar], False, k_n, cfg)
    ta = ta.at[ybar].set(ta_n)
    w = w.at[ybar].set(w_n)
    return TMParams(ta_state=ta, weights=w)


def _feedback_probs(cfg: TMConfig) -> tuple[float, float]:
    p_inc = 1.0 if cfg.boost_true_positive else (cfg.s - 1.0) / cfg.s
    return p_inc, 1.0 / cfg.s


@partial(jax.jit, static_argnames=("cfg",))
def train_epoch(params: TMParams, xs: jnp.ndarray, ys: jnp.ndarray,
                key: jax.Array, cfg: TMConfig) -> TMParams:
    """One sample-sequential pass over (xs, ys) — the paper's local epoch.

    On the kernel path the whole epoch is a single fused ``pallas_call``
    (clause banks stay in VMEM across samples) under the reference key
    discipline, the Type-I coins hashed inside it — bit-identical to
    the scan below, pinned by ``tests/test_tm.py``.
    """
    if cfg.use_kernel and cfg.weighted:
        from repro.kernels import draws as kdraws
        from repro.kernels import ops as kops
        p_inc, p_dec = _feedback_probs(cfg)
        offs, act, coin_keys, order = kdraws.epoch_draws(
            key, xs.shape[0], cfg.n_clauses, cfg.n_classes)
        ys32 = ys.astype(jnp.int32)
        cls2 = jnp.stack([ys32, (ys32 + offs) % cfg.n_classes], axis=-1)
        ta, w, _ = kops.train_epoch_fused(
            params.ta_state[None], params.weights[None],
            literals(xs)[None], cls2[None], act[None], coin_keys[None],
            order[None], n_states=cfg.n_states, T=cfg.T, p_inc=p_inc,
            p_dec=p_dec)
        return TMParams(ta_state=ta[0], weights=w[0])

    def step(p, inp):
        lits, y, k = inp
        return _train_one_sample(p, lits, y, k, cfg), None

    keys = jax.random.split(key, xs.shape[0])
    params, _ = jax.lax.scan(step, params, (literals(xs), ys, keys))
    return params


@partial(jax.jit, static_argnames=("cfg", "epochs"))
def train(params: TMParams, xs: jnp.ndarray, ys: jnp.ndarray,
          key: jax.Array, cfg: TMConfig, epochs: int = 1) -> TMParams:
    def body(p, k):
        return train_epoch(p, xs, ys, k, cfg), None
    params, _ = jax.lax.scan(body, params, jax.random.split(key, epochs))
    return params


# ---------------------------------------------------------------------------
# Client-batched entry points (federated rounds; tm_backend="pallas")
# ---------------------------------------------------------------------------
# All three take params/data with a leading client axis N.  On the
# reference path they are plain vmaps of the per-client functions; on the
# kernel path the whole round is one client-batched kernel launch, which
# is the fast shape (vmap of a pallas_call serializes clients via grid
# batching).  Outputs are bit-identical either way.

def type1_rows(cfg: TMConfig, n_samples: int, epochs: int) -> int:
    """The Type-I rows one client's training could hash: each sample
    gives each of its two roles ⌈m/2⌉ Type-I rows."""
    return epochs * n_samples * 2 * ((cfg.n_clauses + 1) // 2)


@partial(jax.jit, static_argnames=("cfg", "epochs"))
def train_batched(params: TMParams, xs: jnp.ndarray, ys: jnp.ndarray,
                  keys: jnp.ndarray, cfg: TMConfig, epochs: int = 1
                  ) -> tuple[TMParams, jnp.ndarray | None]:
    """params stacked (N, ...); xs (N,S,o); ys (N,S); keys (N,2).

    Returns the trained params and, on the kernel path, the (N,) int32
    Type-I rows each client's epochs hashed (of :func:`type1_rows`);
    ``None`` on the reference path, which draws every coin."""
    if not (cfg.use_kernel and cfg.weighted):
        return jax.vmap(
            lambda p, x, y, k: train(p, x, y, k, cfg, epochs)
        )(params, xs, ys, keys), None

    from repro.kernels import draws as kdraws
    from repro.kernels import ops as kops
    p_inc, p_dec = _feedback_probs(cfg)
    n_samples = ys.shape[1]
    lits = literals(xs)
    ys32 = ys.astype(jnp.int32)
    # (epochs, N, key): same per-client split(key, epochs) as train()
    ekeys = jnp.swapaxes(
        jax.vmap(lambda k: jax.random.split(k, epochs))(keys), 0, 1)

    def epoch_body(carry, ek):
        ta, w, hashed = carry
        # the scope names the epoch's draws, coin keys and row orders in
        # the device trace (op metadata only: the compiled program is
        # the same)
        with jax.named_scope("tm.draws"):
            offs, act, coin_keys, order = jax.vmap(
                lambda k: kdraws.epoch_draws(k, n_samples, cfg.n_clauses,
                                             cfg.n_classes))(ek)
            cls2 = jnp.stack([ys32, (ys32 + offs) % cfg.n_classes],
                             axis=-1)
        ta, w, h = kops.train_epoch_fused(ta, w, lits, cls2, act,
                                          coin_keys, order,
                                          n_states=cfg.n_states, T=cfg.T,
                                          p_inc=p_inc, p_dec=p_dec)
        return (ta, w, hashed + h), None

    hashed = jnp.zeros(ys.shape[:1], jnp.int32)
    (ta, w, hashed), _ = jax.lax.scan(
        epoch_body, (params.ta_state, params.weights, hashed), ekeys)
    return TMParams(ta_state=ta, weights=w), hashed


@partial(jax.jit, static_argnames=("cfg", "weighted"))
def confidence_scores_batched(params: TMParams, x_conf: jnp.ndarray,
                              cfg: TMConfig,
                              weighted: bool = False) -> jnp.ndarray:
    """Stacked confidence margins: params (N, ...), x_conf (N,B,o) → (N,C)."""
    if not cfg.use_kernel:
        return jax.vmap(
            lambda p, x: confidence_scores(p, x, cfg, weighted)
        )(params, x_conf)

    from repro.kernels import ops as kops
    include = (params.ta_state > cfg.n_states).astype(jnp.int32)
    pol = clause_polarity(cfg)
    if weighted:
        wpol = pol[None, None, :] * params.weights
    else:
        wpol = jnp.broadcast_to(pol[None, None, :], params.weights.shape)
    margin = kops.fused_votes_batched(include, literals(x_conf), wpol,
                                      predict=True)  # (N, B, C)
    return margin.sum(axis=1)


@partial(jax.jit, static_argnames=("cfg",))
def predict_batched(params: TMParams, x: jnp.ndarray,
                    cfg: TMConfig) -> jnp.ndarray:
    """Stacked predictions: params (N, ...), x (N,B,o) → (N,B) int32.

    The client-batched inference primitive: on the kernel path the
    whole heterogeneous batch — N distinct models, e.g. one per client
    of a mixed-cluster serving request — is a single
    ``fused_votes_batched`` launch, clipped to ±T before the argmax
    exactly like :func:`predict`.  The reference path is a plain vmap
    of :func:`predict`; outputs are bit-identical either way (the
    serving conformance tests pin it)."""
    if not cfg.use_kernel:
        return jax.vmap(lambda p, xx: predict(p, xx, cfg))(params, x)

    from repro.kernels import ops as kops
    include = (params.ta_state > cfg.n_states).astype(jnp.int32)
    pol = clause_polarity(cfg)
    w = params.weights if cfg.weighted else jnp.ones_like(params.weights)
    votes = kops.fused_votes_batched(include, literals(x),
                                     pol[None, None, :] * w, predict=True)
    return jnp.argmax(jnp.clip(votes, -cfg.T, cfg.T), axis=-1)


@partial(jax.jit, static_argnames=("cfg",))
def accuracy_batched(params: TMParams, x: jnp.ndarray, y: jnp.ndarray,
                     cfg: TMConfig) -> jnp.ndarray:
    """Stacked accuracy: params (N, ...), x (N,B,o), y (N,B) → (N,)."""
    if not cfg.use_kernel:
        return jax.vmap(
            lambda p, xx, yy: accuracy(p, xx, yy, cfg))(params, x, y)
    # same math as the vmapped path, via the one batched-votes kernel —
    # serving parity is by construction: eval and serve share this
    return (predict_batched(params, x, cfg) == y).mean(axis=-1)
