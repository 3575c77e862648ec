"""The ``Strategy`` protocol v2: every federated method as one interface.

A strategy reduces a federated method to the pieces the engine can
orchestrate uniformly:

* ``init(key, n_clients, data)``  → (stacked client state, :class:`ServerState`)
* ``client_step(cs, slots, d, key)`` → (new client state, :class:`Upload`)
* ``apply_broadcast(cs, slots, slot_matrix)`` → new client state
* ``evaluate(cs, x, y)``          → scalar accuracy

plus two *optional server-side hooks* (the v2 additions):

* ``assign(server, vecs, slots, arrive) → slots`` — recompute the slot
  id of every upload **server-side, per round**, between uplink-decode
  and aggregation.  This is what lets FLIS (Morafah et al. 2023) derive
  cluster membership each round from inference similarity on a
  server-held probe set: shapes stay static (at most ``n_slots`` rows)
  while *membership* is fully dynamic.  Strategies without the hook
  keep their client-proposed slot ids (TPFL's confidence argmax, IFCA's
  loss-minimizing choice — those need client-local data, so they stay
  in ``client_step`` and flow through the same aggregation path).
* ``server_update(server, agg, counts) → server`` — fold the per-slot
  aggregate into the server state.  Replaces the engine's hard-coded
  in-place row write: strategies control empty-slot retention, server
  momentum, and any auxiliary bookkeeping (FLIS records the round's
  cluster-membership table).  :func:`default_server_update` is the
  Alg. 2 rule (slots with contributors take the aggregate, empty slots
  keep their previous row bit-for-bit) and is what the engine applies
  when a strategy defines no hook.

Server state is a strategy-owned pytree, :class:`ServerState`: the
``(n_slots, vec_dim)`` slot matrix that rides the wire, plus an ``aux``
pytree the strategy alone interprets (FLIS: the probe set and the
membership table).  It is carried in ``EngineState``, checkpointed with
it, and restored loudly on layout drift (see
``runtime/checkpointing.py``).

The unifying trick is unchanged from v1: every method's round
contribution is expressed as ``j`` flat float32 vectors, each tagged
with a server slot id (slot = cluster).  TPFL uploads its
``top_classes`` clause-weight vectors tagged by class; FedAvg/FedProx
upload the flattened MLP tagged slot 0; IFCA uploads the flattened MLP
tagged with the loss-minimizing cluster; FLIS uploads the flattened MLP
with a placeholder tag that ``assign`` replaces server-side; FedTM
uploads the full ``(C·m)`` TM weight block into one global slot.
Aggregation is then always a (masked, optionally staleness-weighted)
per-slot mean — the same masked reduction
:mod:`repro.fl.masked_collectives` lowers to a single collective on a
mesh — and the engine's scheduler/codec/async machinery applies to
every method unchanged.  Slot id −1 means "nothing shared in this
slot" and is ignored by aggregation and broadcast.

``TPFLStrategy.client_step`` / ``apply_broadcast`` are *the* Alg. 1 /
Phase-D implementations — ``repro.core.federation`` vmaps them, so the
legacy driver and the runtime engine share one source of truth.
Likewise :func:`flis_similarity` / :func:`flis_dc_labels` /
:func:`flis_hc_labels` are shared with the ``core/baselines.py``
reference loops the conformance suite pins the engine against.

The ``slots`` matrix a ``client_step`` receives is what the client
*holds*, not what the aggregator stores: under a lossy wire codec the
engine hands in the codec-roundtripped broadcast rows
(``Engine.wire_tx_server``), so strategies that warm-start from global
state (FedAvg/FedProx/IFCA) train from exactly the precision the wire
carried.  TPFL, FLIS and FedTM delete it unread — their clients train
from their own state (which already holds last round's broadcast).

Per-shard lowering contract
---------------------------
The engine's shard-mapped backend (``runtime/executors.py``) runs
``client_step`` / ``apply_broadcast`` / ``evaluate`` *inside*
``shard_map`` — one block of sampled clients per shard, the slot matrix
replicated.  ``assign`` and ``server_update`` are *replicated* server
math: the executor all_gathers the round's uploads into canonical
client order, every shard computes the identical assignment, and each
slices back its own block.  That imposes the same requirements as v1,
pinned per (strategy × codec × participation) cell by the conformance
suite:

* pure jax, per-client for the client hooks (no host callbacks, no
  data-dependent shapes, no reads of any *other* client's row);
  ``assign`` is the one place cross-client math is allowed, and it must
  be a pure function of (server state, the round's uploads, arrival);
* ``Upload.vecs`` float32 ``(j_slots, vec_dim)`` and ``Upload.slots``
  int32 ``(j_slots,)`` exactly — the wire codec and the masked
  collective type-pun on this framing;
* a strategy instance is hashable (frozen dataclass) and equality-
  stable, because the shard-mapped stage programs cache compiled
  executables keyed on it (``jax.jit`` static argument).  Anything
  array-valued therefore belongs in ``ServerState`` (traced), never in
  a strategy field.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Literal, NamedTuple, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.core import mlp, tm
from repro.data.partition import ClientData

DOWNLOADS = ("assigned", "all_slots")


class Upload(NamedTuple):
    vecs: jnp.ndarray    # (j, d) float32 — what goes on the wire
    slots: jnp.ndarray   # (j,)   int32   — target server slot, −1 = none


class ServerState(NamedTuple):
    """Strategy-owned server state: the wire-visible slot matrix plus an
    opaque aux pytree only the strategy interprets (probe sets,
    membership tables, momentum...).  Carried in ``EngineState`` and
    checkpointed as one pytree."""

    slots: jnp.ndarray   # (n_slots, d) float32 — rows that ride the wire
    aux: Any = ()        # strategy-private pytree (empty for most)


def ensure_server_state(server) -> ServerState:
    """Coerce a v1 ``init`` return (bare slot matrix) into v2 form."""
    if isinstance(server, ServerState):
        return server
    return ServerState(slots=jnp.asarray(server, jnp.float32))


def default_server_update(server: ServerState, agg: jnp.ndarray,
                          counts: jnp.ndarray) -> ServerState:
    """The Alg. 2 retention rule: slots that received contributors take
    the aggregate, empty slots keep their previous row bit-for-bit."""
    return server._replace(
        slots=jnp.where(counts[:, None] > 0, agg, server.slots))


def resolve_server_update(strategy):
    """The strategy's ``server_update`` hook, or the Alg. 2 default."""
    return getattr(strategy, "server_update", None) or default_server_update


@runtime_checkable
class Strategy(Protocol):
    n_slots: int          # rows in the server slot matrix
    vec_dim: int          # d — length of one uploaded vector
    j_slots: int          # uploads per client per round
    downloads: Literal["assigned", "all_slots"]   # validated at engine init

    def init(self, key: jax.Array, n_clients: int,
             data: ClientData | None = None): ...
    def client_step(self, cs, slots: jnp.ndarray, d: ClientData,
                    key: jax.Array): ...
    def apply_broadcast(self, cs, slots: jnp.ndarray,
                        slot_matrix: jnp.ndarray): ...
    def evaluate(self, cs, x: jnp.ndarray, y: jnp.ndarray): ...
    # optional hooks (absence = v1 behaviour):
    #   assign(server: ServerState, vecs (K,j,d), slots (K,j),
    #          arrive (K,)) -> (K,j) int32
    #   server_update(server: ServerState, agg (C,d), counts (C,))
    #          -> ServerState


# ---------------------------------------------------------------------------
# TPFL (paper Alg. 1 + Phase D)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TPFLStrategy:
    """Confidence-clustered selective sharing on the Tsetlin Machine."""

    tm_cfg: tm.TMConfig
    local_epochs: int = 10
    top_classes: int = 1                 # j — §7 multi-cluster extension
    conf_threshold: float | None = None  # §7 confidence gate (−1 below)
    weighted_confidence: bool = False    # Alg. 1 uses unweighted margins

    downloads: str = dataclasses.field(default="assigned", init=False)

    @property
    def n_slots(self) -> int:
        return self.tm_cfg.n_classes

    @property
    def vec_dim(self) -> int:
        return self.tm_cfg.n_clauses

    @property
    def j_slots(self) -> int:
        return self.top_classes

    def init(self, key: jax.Array, n_clients: int,
             data: ClientData | None = None):
        del data
        keys = jax.random.split(key, n_clients)
        params = jax.vmap(lambda k: tm.init_params(self.tm_cfg, k))(keys)
        server = jnp.zeros((self.n_slots, self.vec_dim), jnp.float32)
        return params, ServerState(server)

    # --- O(K) init hooks (client_store="mmap") ----------------------------
    # The store regenerates never-spilled rows on demand, so init must be
    # expressible per-cohort: ``init_cohort(key, ids, n) ==
    # init(key, n)[0][ids]`` bit-for-bit (same key split, indexed), and
    # ``init_server`` is the server part alone.  Only the per-client key
    # table is O(N) — 8 bytes/client, transient.

    def init_cohort(self, key: jax.Array, ids, n_clients: int):
        keys = jax.random.split(key, n_clients)[jnp.asarray(ids)]
        return jax.vmap(lambda k: tm.init_params(self.tm_cfg, k))(keys)

    def init_server(self, key: jax.Array, n_clients: int) -> ServerState:
        del key, n_clients
        return ServerState(
            jnp.zeros((self.n_slots, self.vec_dim), jnp.float32))

    def client_step(self, cs: tm.TMParams, slots: jnp.ndarray,
                    d: ClientData, key: jax.Array):
        """Alg. 1: local TM training, per-class confidence, selective
        upload of the ``top_classes`` most-confident weight vectors."""
        del slots  # TPFL clients never read global state before training
        cfg = self.tm_cfg
        params = tm.train(cs, d.x_train, d.y_train, key, cfg,
                          epochs=self.local_epochs)
        conf = tm.confidence_scores(params, d.x_conf, cfg,
                                    weighted=self.weighted_confidence)
        vals, c_top = jax.lax.top_k(conf, self.top_classes)       # (j,)
        if self.conf_threshold is not None:
            c_top = jnp.where(vals >= self.conf_threshold, c_top, -1)
        vecs = params.weights[jnp.clip(c_top, 0)].astype(jnp.float32)
        # slot −1 means "share nothing" — its payload row must be zero,
        # not class 0's weights, or the wire meters bytes for frames the
        # server drops (conformance pins the corrected totals).
        vecs = jnp.where((c_top >= 0)[..., None], vecs, 0.0)
        return params, Upload(vecs, c_top.astype(jnp.int32))

    @staticmethod
    def apply_broadcast(cs: tm.TMParams, slots: jnp.ndarray,
                        slot_matrix: jnp.ndarray) -> tm.TMParams:
        """Phase D: overwrite each shared class with its cluster mean.

        A staticmethod so ``federation._phase_d`` can call it without
        materializing a strategy (it needs no config)."""
        new_w = jnp.round(slot_matrix[jnp.clip(slots, 0)]).astype(jnp.int32)

        def one(wc, c_nw):
            c, nwv = c_nw
            return jnp.where(c >= 0, wc.at[c].set(nwv), wc), None

        wc, _ = jax.lax.scan(one, cs.weights, (slots, new_w))
        return cs._replace(weights=wc)

    def evaluate(self, cs: tm.TMParams, x: jnp.ndarray,
                 y: jnp.ndarray) -> jnp.ndarray:
        return tm.accuracy(cs, x, y, self.tm_cfg)

    def predict_batched(self, cs: tm.TMParams,
                        x: jnp.ndarray) -> jnp.ndarray:
        """Stacked per-client predictions (N, B, o) → (N, B) — the
        serving plane's batched-inference hook.  Honours
        ``tm_cfg.use_kernel``: one fused-votes launch for the whole
        mixed-cluster batch on the pallas path."""
        return tm.predict_batched(cs, x, self.tm_cfg)

    # --- fused client-batched path (tm_backend="pallas") ------------------
    # One kernel launch for the whole sampled cohort instead of a vmap of
    # per-client steps (vmap of a pallas_call serializes clients).  The
    # executors dispatch here when ``use_fused_kernels`` is set; outputs
    # are bit-identical to the vmapped ``client_step``/``evaluate``.

    @property
    def use_fused_kernels(self) -> bool:
        return self.tm_cfg.use_kernel

    def fused_client_step(self, cs: tm.TMParams, slots: jnp.ndarray,
                          d: ClientData, keys: jnp.ndarray):
        del slots
        cfg = self.tm_cfg
        params = tm.train_batched(cs, d.x_train, d.y_train, keys, cfg,
                                  epochs=self.local_epochs)
        conf = tm.confidence_scores_batched(
            params, d.x_conf, cfg, weighted=self.weighted_confidence)
        vals, c_top = jax.lax.top_k(conf, self.top_classes)     # (N, j)
        if self.conf_threshold is not None:
            c_top = jnp.where(vals >= self.conf_threshold, c_top, -1)
        rows = jnp.arange(c_top.shape[0])[:, None]
        vecs = params.weights[rows, jnp.clip(c_top, 0)].astype(jnp.float32)
        vecs = jnp.where((c_top >= 0)[..., None], vecs, 0.0)
        return params, Upload(vecs, c_top.astype(jnp.int32))

    def fused_evaluate(self, cs: tm.TMParams, x: jnp.ndarray,
                       y: jnp.ndarray) -> jnp.ndarray:
        return tm.accuracy_batched(cs, x, y, self.tm_cfg)


# ---------------------------------------------------------------------------
# MLP flatten/unflatten (FedAvg / FedProx / IFCA / FLIS wire format)
# ---------------------------------------------------------------------------

def _mlp_layout(n_features: int, n_hidden: int, n_classes: int):
    return (("w1", (n_features, n_hidden)), ("b1", (n_hidden,)),
            ("w2", (n_hidden, n_classes)), ("b2", (n_classes,)))


def _flatten_mlp(params: mlp.Params, layout) -> jnp.ndarray:
    return jnp.concatenate([params[k].astype(jnp.float32).ravel()
                            for k, _ in layout])


def _unflatten_mlp(vec: jnp.ndarray, layout) -> mlp.Params:
    out, off = {}, 0
    for k, shape in layout:
        size = 1
        for s in shape:
            size *= s
        out[k] = vec[off:off + size].reshape(shape)
        off += size
    return out


@dataclasses.dataclass(frozen=True)
class MLPStrategyBase:
    """Shared substrate of the DL strategies (FedAvg/FedProx, IFCA,
    FLIS): one MLP layout, one flatten/unflatten wire format, one
    slot-row broadcast-apply, one evaluation.  Subclasses differ only
    in *routing* — which slot an upload targets and which row a client
    applies — which is exactly the part the v2 assign/aggregate path
    makes uniform."""

    n_features: int
    n_hidden: int
    n_classes: int
    local_epochs: int = 10
    batch: int = 32
    lr: float = 0.05

    @property
    def _layout(self):
        return _mlp_layout(self.n_features, self.n_hidden, self.n_classes)

    @property
    def vec_dim(self) -> int:
        total = 0
        for _, shape in self._layout:
            size = 1
            for s in shape:
                size *= s
            total += size
        return total

    def _stack(self, template: mlp.Params, n_clients: int):
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_clients,) + a.shape), template)

    def _apply_slot_row(self, cs: mlp.Params, slot: jnp.ndarray,
                        slot_matrix: jnp.ndarray) -> mlp.Params:
        """Apply the row this client was routed to; slot −1 = nothing
        was aggregated for this client's round, so it keeps the locally
        trained model instead of an un-updated global."""
        new = _unflatten_mlp(slot_matrix[jnp.clip(slot, 0)], self._layout)
        return jax.tree.map(lambda n, o: jnp.where(slot >= 0, n, o),
                            new, cs)

    def apply_broadcast(self, cs: mlp.Params, slots: jnp.ndarray,
                        slot_matrix: jnp.ndarray) -> mlp.Params:
        return self._apply_slot_row(cs, slots[0], slot_matrix)

    def evaluate(self, cs: mlp.Params, x: jnp.ndarray,
                 y: jnp.ndarray) -> jnp.ndarray:
        return mlp.accuracy(cs, x, y)

    def predict_batched(self, cs: mlp.Params,
                        x: jnp.ndarray) -> jnp.ndarray:
        """Stacked per-client predictions (N, B, o) → (N, B) int32."""
        return jax.vmap(
            lambda p, xx: jnp.argmax(mlp.apply(p, xx), axis=-1)
        )(cs, x).astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class FedAvgStrategy(MLPStrategyBase):
    """FedAvg (and FedProx with ``prox_mu > 0``): one global slot."""

    prox_mu: float = 0.0          # > 0 → FedProx proximal objective

    n_slots: int = dataclasses.field(default=1, init=False)
    j_slots: int = dataclasses.field(default=1, init=False)
    downloads: str = dataclasses.field(default="assigned", init=False)

    def init(self, key: jax.Array, n_clients: int,
             data: ClientData | None = None):
        del data
        g = mlp.init(key, self.n_features, self.n_hidden, self.n_classes)
        server = _flatten_mlp(g, self._layout)[None, :]
        return self._stack(g, n_clients), ServerState(server)

    def client_step(self, cs: mlp.Params, slots: jnp.ndarray,
                    d: ClientData, key: jax.Array):
        start = _unflatten_mlp(slots[0], self._layout)
        ref = start if self.prox_mu > 0 else None
        p = mlp.local_train(start, d.x_train, d.y_train, key,
                            epochs=self.local_epochs, batch=self.batch,
                            lr=self.lr, prox_mu=self.prox_mu, prox_ref=ref)
        return p, Upload(_flatten_mlp(p, self._layout)[None, :],
                         jnp.zeros((1,), jnp.int32))


@dataclasses.dataclass(frozen=True)
class IFCAStrategy(MLPStrategyBase):
    """IFCA: k global models; clients pick by lowest local loss.

    The loss-minimizing estimate needs client-local data, so it stays
    in ``client_step`` (there is nothing server-side to recompute — the
    server trusts the proposed slot id); the upload then flows through
    the same uniform assign/aggregate/server_update pipeline as every
    other strategy."""

    k: int = 10

    j_slots: int = dataclasses.field(default=1, init=False)
    downloads: str = dataclasses.field(default="all_slots", init=False)

    @property
    def n_slots(self) -> int:
        return self.k

    def init(self, key: jax.Array, n_clients: int,
             data: ClientData | None = None):
        del data
        ks = jax.random.split(key, self.k)
        server = jnp.stack([
            _flatten_mlp(mlp.init(kk, self.n_features, self.n_hidden,
                                  self.n_classes), self._layout)
            for kk in ks])
        g = _unflatten_mlp(server[0], self._layout)
        return self._stack(g, n_clients), ServerState(server)

    def client_step(self, cs: mlp.Params, slots: jnp.ndarray,
                    d: ClientData, key: jax.Array):
        def loss_of(vec):
            return mlp.loss_fn(_unflatten_mlp(vec, self._layout),
                               d.x_train, d.y_train)

        choice = jnp.argmin(jax.vmap(loss_of)(slots))
        start = _unflatten_mlp(slots[choice], self._layout)
        p = mlp.local_train(start, d.x_train, d.y_train, key,
                            epochs=self.local_epochs, batch=self.batch,
                            lr=self.lr)
        return p, Upload(_flatten_mlp(p, self._layout)[None, :],
                         choice.astype(jnp.int32)[None])


# ---------------------------------------------------------------------------
# FLIS: dynamic clusters from inference similarity on a probe set
# ---------------------------------------------------------------------------

def flis_similarity(flat_models: jnp.ndarray, probe: jnp.ndarray,
                    layout) -> jnp.ndarray:
    """Pairwise inference similarity of K uploaded models on the probe
    set: cosine similarity of the flattened softmax prediction
    profiles.  ``(K, d) × (P, F) → (K, K)``.  Shared by the engine's
    ``FLISStrategy.assign`` and the ``core/baselines.py`` reference
    loop, so the two compute bit-identical matrices."""
    def profile(vec):
        return jax.nn.softmax(mlp.apply(_unflatten_mlp(vec, layout), probe))

    preds = jax.vmap(profile)(flat_models)            # (K, P, C)
    flat = preds.reshape(flat_models.shape[0], -1)
    flat = flat / jnp.linalg.norm(flat, axis=1, keepdims=True)
    return flat @ flat.T


def flis_dc_labels(sim: jnp.ndarray, arrive: jnp.ndarray,
                   threshold: float, max_slots: int) -> jnp.ndarray:
    """FLIS-DC: connected components of the thresholded similarity
    graph, jit-ably.  Min-label propagation for (static) K steps yields
    each arrived client's component representative (its minimum member
    index); components are then densely renumbered in order of first
    appearance — exactly the labelling of the host reference
    ``baselines._similarity_clusters`` — and clipped into the
    ``max_slots`` server rows (overflow components share the last row).
    Non-arrived clients get −1.  Shapes are static; membership is
    dynamic."""
    k = sim.shape[0]
    arrive = arrive.astype(bool)
    adj = (sim >= threshold) & arrive[:, None] & arrive[None, :]
    labels = jnp.where(arrive, jnp.arange(k, dtype=jnp.int32), k)

    def step(lab, _):
        cand = jnp.where(adj, lab[None, :], k)
        return jnp.minimum(lab, cand.min(axis=1)).astype(jnp.int32), None

    labels, _ = jax.lax.scan(step, labels, None, length=k)
    is_rep = arrive & (labels == jnp.arange(k))
    rank = jnp.cumsum(is_rep.astype(jnp.int32)) - 1    # dense id at rep idx
    dense = rank[jnp.clip(labels, 0, k - 1)]
    dense = jnp.minimum(dense, max_slots - 1)
    return jnp.where(arrive, dense, -1).astype(jnp.int32)


def flis_hc_labels(sim: jnp.ndarray, arrive: jnp.ndarray,
                   threshold: float, max_slots: int) -> jnp.ndarray:
    """FLIS-HC: average-linkage agglomerative clustering of the
    similarity matrix, jit-ably.  K−1 masked merge steps: each step
    merges the pair of active clusters with the highest average
    cross-similarity, while that maximum stays ≥ ``threshold`` — or
    unconditionally while more than ``max_slots`` clusters remain (the
    server has that many rows).  Merges always fold the larger index
    into the smaller, so a cluster's root is its minimum member index
    and the dense renumbering matches the DC convention.  Arithmetic is
    step-for-step identical to the host reference
    ``baselines._average_linkage_clusters`` (same float32 adds, same
    row-major argmax tie-break), which the conformance suite pins."""
    k = sim.shape[0]
    arrive = arrive.astype(bool)
    eye = jnp.eye(k, dtype=bool)
    size = jnp.where(arrive, 1.0, 0.0).astype(jnp.float32)
    cross = jnp.where(arrive[:, None] & arrive[None, :] & ~eye,
                      sim.astype(jnp.float32), 0.0)
    labels = jnp.where(arrive, jnp.arange(k, dtype=jnp.int32), k)
    carry = (cross, size, arrive, labels, jnp.zeros((), bool))

    def step(carry, _):
        cross, size, active, labels, done = carry
        pair_ok = active[:, None] & active[None, :] & ~eye
        avg = jnp.where(
            pair_ok,
            cross / jnp.maximum(size[:, None] * size[None, :], 1.0),
            -jnp.inf)
        flat_i = jnp.argmax(avg)            # row-major first max → a < b
        a, b = flat_i // k, flat_i % k
        best = avg.reshape(-1)[flat_i]
        n_active = active.sum()
        merge = (~done) & jnp.isfinite(best) & (n_active > 1) \
            & ((n_active > max_slots) | (best >= threshold))
        row = cross[a] + cross[b]
        row = row.at[a].set(0.0).at[b].set(0.0)
        cross2 = cross.at[a, :].set(row).at[:, a].set(row)
        cross2 = cross2.at[b, :].set(0.0).at[:, b].set(0.0)
        size2 = size.at[a].add(size[b]).at[b].set(0.0)
        active2 = active.at[b].set(False)
        labels2 = jnp.where(labels == b, a, labels)
        out = (jnp.where(merge, cross2, cross),
               jnp.where(merge, size2, size),
               jnp.where(merge, active2, active),
               jnp.where(merge, labels2, labels),
               done | ~merge)
        return out, None

    if k > 1:
        carry, _ = jax.lax.scan(step, carry, None, length=k - 1)
    cross, size, active, labels, done = carry
    rank = jnp.cumsum(active.astype(jnp.int32)) - 1
    dense = rank[jnp.clip(labels, 0, k - 1)]
    return jnp.where(arrive, dense, -1).astype(jnp.int32)


class FLISAux(NamedTuple):
    """FLIS's strategy-owned server aux: the shared unlabeled probe set
    (server-side, the standard FLIS assumption) and the last round's
    cluster-membership table (contributor count per slot)."""

    probe: jnp.ndarray     # (probe_size, n_features)
    members: jnp.ndarray   # (n_slots,) float32 — last round's counts


class FLISClientState(NamedTuple):
    """FLIS per-client state: the MLP plus the cluster row the client
    last *applied* — the ride-along that lets sparse-delta uplinks
    encode against the row the client actually holds instead of the
    conservative zero reference."""

    params: mlp.Params
    prev_slot: jnp.ndarray   # () int32 — last applied cluster id, 0 at init


@dataclasses.dataclass(frozen=True)
class FLISStrategy(MLPStrategyBase):
    """FLIS (Morafah et al. 2023 flavour): cluster membership derived
    *server-side each round* from inference similarity on a probe set.

    Clients train from their own state (which holds last round's
    cluster model) and upload the flattened MLP tagged with the cluster
    row they last *applied* (``prev_slot``, 0 before the first
    broadcast) — they still do not know this round's cluster; the
    :meth:`assign` hook discards the tag and recomputes membership from
    the decoded uploads (DC = thresholded connected components, HC =
    average-linkage agglomerative), capped at ``max_slots`` server
    rows.  :meth:`server_update` applies the Alg. 2 retention and
    records the round's membership table in ``aux.members``.  The tag's
    one job is the wire codec: sparse-delta uplinks encode against the
    tracked reference of the row the client actually holds, which is a
    far nearer reference than the zero row the old placeholder tag
    forced, so deltas stay small whenever membership is sticky.

    Works under both aggregation modes: sync runs :meth:`assign` as a
    round-synchronous server stage; async runs it over the *matured
    buffer contents* at aggregation time (the engine's host buffer
    path), so membership is recomputed from whichever uploads actually
    arrived together."""

    max_slots: int = 8
    probe_size: int = 64
    threshold: float = 0.9
    linkage: str = "dc"            # dc | hc

    j_slots: int = dataclasses.field(default=1, init=False)
    downloads: str = dataclasses.field(default="assigned", init=False)

    def __post_init__(self):
        if self.linkage not in ("dc", "hc"):
            raise ValueError(f"unknown FLIS linkage {self.linkage!r}; "
                             f"choose 'dc' or 'hc'")

    @property
    def n_slots(self) -> int:
        return self.max_slots

    def init(self, key: jax.Array, n_clients: int,
             data: ClientData | None = None):
        if data is None:
            raise ValueError(
                "FLISStrategy.init needs the engine's ClientData: the "
                "server-side probe set is drawn from the confidence "
                "split (x_conf)")
        k_params, k_probe = jax.random.split(key)
        stacked = jax.vmap(lambda k: mlp.init(
            k, self.n_features, self.n_hidden, self.n_classes))(
            jax.random.split(k_params, n_clients))
        pool = data.x_conf.reshape(-1, self.n_features)
        if self.probe_size > pool.shape[0]:
            raise ValueError(
                f"probe_size={self.probe_size} exceeds the confidence "
                f"split's pooled sample count ({pool.shape[0]}) — the "
                f"probe set is drawn without replacement from x_conf; "
                f"lower --probe-size or enlarge the conf split")
        idx = jax.random.choice(k_probe, pool.shape[0], (self.probe_size,),
                                replace=False)
        server = jnp.zeros((self.n_slots, self.vec_dim), jnp.float32)
        aux = FLISAux(probe=pool[idx],
                      members=jnp.zeros((self.n_slots,), jnp.float32))
        cs = FLISClientState(
            stacked, jnp.zeros((n_clients,), jnp.int32))
        return cs, ServerState(server, aux)

    def client_step(self, cs: FLISClientState, slots: jnp.ndarray,
                    d: ClientData, key: jax.Array):
        del slots  # clients train from their own (cluster-model) state
        p = mlp.local_train(cs.params, d.x_train, d.y_train, key,
                            epochs=self.local_epochs, batch=self.batch,
                            lr=self.lr)
        return (FLISClientState(p, cs.prev_slot),
                Upload(_flatten_mlp(p, self._layout)[None, :],
                       cs.prev_slot[None]))   # tag = last applied row

    def apply_broadcast(self, cs: FLISClientState, slots: jnp.ndarray,
                        slot_matrix: jnp.ndarray) -> FLISClientState:
        """Apply the routed row and remember it: ``prev_slot`` advances
        only when a row was actually applied (slot −1 keeps both the
        local model and the old tag)."""
        return FLISClientState(
            self._apply_slot_row(cs.params, slots[0], slot_matrix),
            jnp.where(slots[0] >= 0, slots[0], cs.prev_slot))

    def evaluate(self, cs: FLISClientState, x: jnp.ndarray,
                 y: jnp.ndarray) -> jnp.ndarray:
        return mlp.accuracy(cs.params, x, y)

    def predict_batched(self, cs: FLISClientState,
                        x: jnp.ndarray) -> jnp.ndarray:
        return super().predict_batched(cs.params, x)

    def assign(self, server: ServerState, vecs: jnp.ndarray,
               slots: jnp.ndarray, arrive: jnp.ndarray) -> jnp.ndarray:
        """The FLIS server step: inference similarity on the probe set →
        DC/HC clustering of the arrived uploads into at most
        ``max_slots`` dynamic clusters."""
        del slots                      # placeholder tags carry no signal
        sim = flis_similarity(vecs[:, 0, :], server.aux.probe, self._layout)
        if self.linkage == "dc":
            lab = flis_dc_labels(sim, arrive, self.threshold, self.n_slots)
        else:
            lab = flis_hc_labels(sim, arrive, self.threshold, self.n_slots)
        return lab[:, None]

    def server_update(self, server: ServerState, agg: jnp.ndarray,
                      counts: jnp.ndarray) -> ServerState:
        """Alg. 2 retention on the rows, plus the round's membership
        table recorded into ``aux`` (checkpointed with the state)."""
        slots = jnp.where(counts[:, None] > 0, agg, server.slots)
        return ServerState(slots, server.aux._replace(members=counts))


# ---------------------------------------------------------------------------
# FedTM: full-weight TM averaging, one global slot, no personalization
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FedTMStrategy:
    """FedTM (Qi et al. 2023 flavour): the same TM as TPFL, but every
    client uploads its *full* ``(C, m)`` weight block into one global
    slot and everyone applies the rounded global mean — no confidence
    clustering, no selective upload.  The TPFL-vs-FedTM delta therefore
    isolates the paper's contribution, now under one engine, one
    scheduler, and one byte-exact wire codec."""

    tm_cfg: tm.TMConfig
    local_epochs: int = 10

    n_slots: int = dataclasses.field(default=1, init=False)
    j_slots: int = dataclasses.field(default=1, init=False)
    downloads: str = dataclasses.field(default="assigned", init=False)

    @property
    def vec_dim(self) -> int:
        return self.tm_cfg.n_classes * self.tm_cfg.n_clauses

    def init(self, key: jax.Array, n_clients: int,
             data: ClientData | None = None):
        del data
        keys = jax.random.split(key, n_clients)
        params = jax.vmap(lambda k: tm.init_params(self.tm_cfg, k))(keys)
        server = jnp.zeros((1, self.vec_dim), jnp.float32)
        return params, ServerState(server)

    # O(K) init hooks — same contract as TPFLStrategy's:
    # init_cohort(key, ids, n) == init(key, n)[0][ids] bit-for-bit
    def init_cohort(self, key: jax.Array, ids, n_clients: int):
        keys = jax.random.split(key, n_clients)[jnp.asarray(ids)]
        return jax.vmap(lambda k: tm.init_params(self.tm_cfg, k))(keys)

    def init_server(self, key: jax.Array, n_clients: int) -> ServerState:
        del key, n_clients
        return ServerState(jnp.zeros((1, self.vec_dim), jnp.float32))

    def client_step(self, cs: tm.TMParams, slots: jnp.ndarray,
                    d: ClientData, key: jax.Array):
        del slots  # clients hold last round's global weights already
        params = tm.train(cs, d.x_train, d.y_train, key, self.tm_cfg,
                          epochs=self.local_epochs)
        vec = params.weights.astype(jnp.float32).reshape(1, -1)
        return params, Upload(vec, jnp.zeros((1,), jnp.int32))

    def apply_broadcast(self, cs: tm.TMParams, slots: jnp.ndarray,
                        slot_matrix: jnp.ndarray) -> tm.TMParams:
        cfg = self.tm_cfg
        new_w = jnp.round(slot_matrix[0]).astype(jnp.int32).reshape(
            cfg.n_classes, cfg.n_clauses)
        w = jnp.where(slots[0] >= 0, new_w, cs.weights)
        return cs._replace(weights=w)

    def evaluate(self, cs: tm.TMParams, x: jnp.ndarray,
                 y: jnp.ndarray) -> jnp.ndarray:
        return tm.accuracy(cs, x, y, self.tm_cfg)

    def predict_batched(self, cs: tm.TMParams,
                        x: jnp.ndarray) -> jnp.ndarray:
        """Stacked per-client predictions (serving hook; honours
        ``tm_cfg.use_kernel``)."""
        return tm.predict_batched(cs, x, self.tm_cfg)

    # --- fused client-batched path (tm_backend="pallas") ------------------

    @property
    def use_fused_kernels(self) -> bool:
        return self.tm_cfg.use_kernel

    def fused_client_step(self, cs: tm.TMParams, slots: jnp.ndarray,
                          d: ClientData, keys: jnp.ndarray):
        del slots
        params = tm.train_batched(cs, d.x_train, d.y_train, keys,
                                  self.tm_cfg, epochs=self.local_epochs)
        n = d.y_train.shape[0]
        vecs = params.weights.astype(jnp.float32).reshape(n, 1, -1)
        return params, Upload(vecs, jnp.zeros((n, 1), jnp.int32))

    def fused_evaluate(self, cs: tm.TMParams, x: jnp.ndarray,
                       y: jnp.ndarray) -> jnp.ndarray:
        return tm.accuracy_batched(cs, x, y, self.tm_cfg)


def build_baseline_strategy(name: str, *, n_features: int, n_classes: int,
                            n_hidden: int = 128, local_epochs: int = 10,
                            batch: int = 32, lr: float = 0.05,
                            prox_mu: float = 0.1,
                            ifca_k: int | None = None,
                            max_slots: int = 8, probe_size: int = 64,
                            flis_threshold: float = 0.9):
    """The one name→Strategy factory for the non-TPFL baselines (shared
    by the CLI and the table-5 benchmark so hyperparameters can't
    drift).  FedTM is built separately (it needs the TM config)."""
    kw = dict(n_features=n_features, n_classes=n_classes,
              n_hidden=n_hidden, local_epochs=local_epochs,
              batch=batch, lr=lr)
    if name == "fedavg":
        return FedAvgStrategy(**kw)
    if name == "fedprox":
        return FedAvgStrategy(prox_mu=prox_mu, **kw)
    if name == "ifca":
        return IFCAStrategy(k=ifca_k or min(10, n_classes), **kw)
    if name in ("flis_dc", "flis_hc"):
        return FLISStrategy(linkage=name.removeprefix("flis_"),
                            max_slots=max_slots, probe_size=probe_size,
                            threshold=flis_threshold, **kw)
    raise ValueError(f"unknown baseline strategy {name!r}")
