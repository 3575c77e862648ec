"""Round executors: where one federated round's compute actually runs.

The engine (``runtime/engine.py``) owns the *semantics* of a round —
scheduling, the wire codec, aggregation mode, checkpointing — and
delegates the three array-heavy pieces (client training, the masked
per-slot mean, broadcast-apply + evaluation) to a ``RoundExecutor``:

* :class:`InProcessExecutor` — eager vmap over the sampled clients, the
  host einsum of ``clustering.aggregate``.  The reference backend.
* :class:`ShardMapExecutor` — the same round lowered through
  ``shard_map`` over a ``clients`` mesh axis: each shard trains its
  block of the sampled clients, and aggregation is a single masked
  collective from :mod:`repro.fl.masked_collectives` (``all_gather`` +
  canonical einsum for bit-exactness, or the C·m ``psum`` accumulator
  for communication-optimality).  For the dominant configuration (sync
  barrier, full participation, dense float32 wire) the *entire* round —
  client_step, aggregation, broadcast-apply, evaluation — is one
  compiled sharded program (:func:`build_sharded_round`, also what the
  dry-run lowers on the production mesh).

Both executors also run the **async buffered update** as one compiled
program (:func:`_buffer_insert` → maturity gate →
staleness-discounted mean): the fixed-capacity upload buffer is device
state carried in ``EngineState`` (see ``docs/async-runtime.md`` for the
lane layout), the insert/evict loop is a ``lax.scan`` of masked
single-row updates, and the gate/mean are branchless ``where`` selects
— no host round-trips.  Shard-mapped, the per-shard uploads are
all_gathered into canonical client order (the buffer is global round
state, so every shard replays the identical insert), and the mean
lowers through ``masked_collectives`` — host-form einsum for
``gather`` (bit-exact), :func:`buffered_weighted_mean_sharded` for
``psum``.

The conformance suite (``tests/test_fl_conformance.py``) pins
shard-mapped == in-process == legacy ``federation.run`` bit-for-bit for
every (strategy, codec, participation) cell — and device-buffered ==
host-buffered == shard-mapped for the async mode; anything that changes
per-client key derivation, reduction shapes, insert order, or merge
order breaks it.

Sampled-K padding: shard_map needs the leading axis divisible by the
mesh axis size, so executors pad K (and N for evaluation) up to the
next multiple with inert rows — repeated row 0 for client state/data,
``active=False`` / slot −1 for participation — and slice the padding
back off.  Padded rows are masked out of the collective *and* trimmed
from the reduction shape (``n_valid``) so the float summation order
matches the unpadded in-process einsum exactly.

Sharding contract, program by program
-------------------------------------
Client-major arrays (client state, per-client data, rng keys, slot
ids, arrival masks, uploads) are sharded ``P(axis)`` — one contiguous
block per shard; the server matrix, cluster counts, the async buffer
lanes, and the round index are replicated ``P()``.

* ``_train_program``       — per-shard vmap of ``client_step``; slot
  matrix replicated in, per-shard (state, uploads) out.  No collective.
* ``_assign_program``      — the v2 server-side assignment stage: one
  tiled ``all_gather`` per upload lane into canonical client order,
  the strategy's ``assign`` hook replayed identically on every shard
  (replicated server state in), per-shard slot-id blocks out.
* ``_agg_program``         — per-shard uploads in, replicated raw
  (mean, counts) out via **one** ``all_gather`` (gather mode) or
  **one** ``psum`` of the (C, m) accumulator (psum mode); empty-slot
  retention is applied by the strategy's ``server_update``.
* ``_apply_program``       — per-shard broadcast-apply/merge; server
  replicated in.  No collective.
* ``_eval_program``        — per-shard vmap of ``evaluate``.  No
  collective.
* ``_fused_program``       — the four above fused (identity wire):
  per-shard in/out except the replicated (server, counts); the same
  single aggregation collective in the middle.
* ``_async_update_program`` / ``build_sharded_async_update`` — uploads
  per-shard in, everything else replicated both ways; one
  ``all_gather`` per upload lane (canonical insert order), plus the
  ``psum`` of :func:`buffered_weighted_mean_sharded` in psum mode.

Telemetry span boundaries (``repro.fl.obs``): the engine wraps each
executor call in a phase span and fences its outputs with
``jax.block_until_ready``, so a stage program's span bills the whole
compiled program — dispatch *and* device execution — to that phase
(``client_step`` = ``_train_program``, ``assign`` =
``_assign_program``, ``aggregate`` = ``_agg_program`` or the async
update, ``apply_merge``/``eval`` likewise, and ``fused_round`` the
whole ``_fused_program``).  Executors stay telemetry-free: nothing
observability-related crosses into compiled code, which is what keeps
obs-on == obs-off bit-exact.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import clustering
from repro.fl import masked_collectives
from repro.fl.runtime.strategy import resolve_server_update

COLLECTIVES = ("gather", "psum")


def applied_slots(slots, counts, arrive):
    """Which slots are actually pushed back to each client this round:
    it arrived, it shared the slot, and the slot received an aggregate
    (a never-fed slot row must not overwrite fresh local training).
    Shared by the engine's staged path and the fused sharded body — the
    bit-parity contract depends on both using exactly this formula."""
    return jnp.where(arrive[:, None] & (slots >= 0)
                     & (counts[jnp.clip(slots, 0)] > 0), slots, -1)


def _broadcast_apply_merge(strategy, new_sub, applied, server, old_sub,
                           recv):
    """vmap ``apply_broadcast`` over clients, then revert non-receivers
    to their pre-round state.  The one merge both backends (and the
    fused round) share — the bit-parity contract depends on every
    execution path using exactly this function."""
    bc_sub = jax.vmap(strategy.apply_broadcast,
                      in_axes=(0, 0, None))(new_sub, applied, server)

    def keep(new, old):
        m = recv.reshape((-1,) + (1,) * (new.ndim - 1))
        return jnp.where(m, new, old)

    return jax.tree.map(keep, bc_sub, old_sub)


def _client_step_block(strategy):
    """The block form of ``client_step`` over a stacked client cohort.

    Default: a vmap of the per-client step.  Strategies that set
    ``use_fused_kernels`` (the engine's ``tm_backend="pallas"``) supply
    ``fused_client_step`` — one client-batched kernel launch instead of
    a vmap (vmap of a ``pallas_call`` batches by prepending a grid axis,
    serializing clients).  Bit-identical outputs either way, so every
    execution path below dispatches through here.  The branch resolves
    at trace time: the strategy is a static (hashable) argument of each
    stage program."""
    if getattr(strategy, "use_fused_kernels", False):
        return strategy.fused_client_step

    def block(cs, server, d, keys):
        return jax.vmap(strategy.client_step,
                        in_axes=(0, None, 0, 0))(cs, server, d, keys)

    return block


def _evaluate_block(strategy):
    """Block form of ``evaluate`` — same dispatch as
    :func:`_client_step_block`."""
    if getattr(strategy, "use_fused_kernels", False):
        return strategy.fused_evaluate
    return lambda cs, x, y: jax.vmap(strategy.evaluate)(cs, x, y)


def evaluate_population(executor, strategy, gather_cs, gather_data,
                        n: int, chunk: int):
    """Full-population evaluation over a host-side client store, in
    fixed-size chunks — the mmap engine's ``store_eval="full"`` path.

    ``gather_cs(ids)`` / ``gather_data(ids) -> (x_test, y_test)`` pull
    each chunk's rows (store gather / streaming ingestion); only
    ``chunk`` clients are ever device-resident.  Per-client evaluation
    is an independent vmap lane on both executors (no cross-client
    reduction — the shard-mapped program pads and trims), so the
    concatenated accuracy vector is bit-identical to one monolithic
    ``executor.evaluate`` over the whole population."""
    accs = []
    for c0 in range(0, n, chunk):
        ids = np.arange(c0, min(c0 + chunk, n), dtype=np.int64)
        cs = gather_cs(ids)
        x, y = gather_data(ids)
        accs.append(np.asarray(executor.evaluate(strategy, cs, x, y)))
    return jnp.asarray(np.concatenate(accs, axis=0))


# ---------------------------------------------------------------------------
# in-process backend (the reference semantics)
# ---------------------------------------------------------------------------

class InProcessExecutor:
    """Eager vmap backend — every round is host-orchestrated jax ops."""

    def train(self, strategy, sub_cs, server, sub_data, keys):
        new_sub, upload = _client_step_block(strategy)(
            sub_cs, server, sub_data, keys)
        return new_sub, upload.vecs, upload.slots     # (K,j,d), (K,j)

    def assign(self, strategy, server, dec, slots, arrive):
        """Run the strategy's server-side assignment hook eagerly (pure
        jax on fully materialized arrays — the reference semantics the
        shard-mapped assign stage is pinned against)."""
        return strategy.assign(server, dec, slots, arrive)

    def masked_mean(self, strategy, dec, slots, arrive):
        """The exact Alg. 2 masked mean (weights all 1), bit-identical
        to ``clustering.aggregate``.  Returns the *raw* per-slot mean
        (zeros where empty) — empty-slot retention is the strategy's
        ``server_update`` decision, applied by the engine."""
        masked = jnp.where(arrive[:, None], slots, -1)
        res = clustering.aggregate(
            dec.reshape(-1, strategy.vec_dim), masked.reshape(-1),
            strategy.n_slots)
        return res.cluster_weights, res.counts

    def apply_merge(self, strategy, new_sub, applied, rx_server, old_sub,
                    recv):
        return _broadcast_apply_merge(strategy, new_sub, applied,
                                      rx_server, old_sub, recv)

    def evaluate(self, strategy, cs, x_test, y_test):
        return _evaluate_block(strategy)(cs, x_test, y_test)

    def async_update(self, strategy, buf, up, round_idx, prev,
                     min_uploads: int):
        """Insert this round's uploads into the device buffer and fold
        in the matured entries — one jitted program on the default
        device (buffer and uploads both unsharded)."""
        return _async_update_program(strategy.n_slots, min_uploads, buf,
                                     up, round_idx, prev)

    def fused_sync_round(self, strategy, sub_cs, server, sub_data, keys,
                         arrive):
        return None                      # no fused form; use the stages


# ---------------------------------------------------------------------------
# shard_map padding helpers
# ---------------------------------------------------------------------------

def _pad_rows(a: jnp.ndarray, mult: int, fill=None) -> jnp.ndarray:
    """Pad the leading axis up to a multiple of ``mult`` — with ``fill``,
    or by repeating row 0 (inert: results for pad rows are sliced off)."""
    pad = (-a.shape[0]) % mult
    if pad == 0:
        return a
    if fill is None:
        tail = jnp.broadcast_to(a[:1], (pad,) + a.shape[1:])
    else:
        tail = jnp.full((pad,) + a.shape[1:], fill, a.dtype)
    return jnp.concatenate([a, tail], axis=0)


def _pad_tree(tree, mult: int):
    return jax.tree.map(lambda a: _pad_rows(a, mult), tree)


def _unpad(tree, n: int):
    return jax.tree.map(lambda a: a[:n], tree)


# ---------------------------------------------------------------------------
# the shard-mapped sync round (one compiled program)
# ---------------------------------------------------------------------------

def _sharded_masked_mean(vals, slots, n_slots, axis, collective, n_valid):
    """Per-shard uploads → replicated raw (mean, counts), one
    collective.  Empty-slot retention is ``server_update``'s decision —
    this returns the bare per-slot mean (zeros where empty)."""
    if collective == "gather":
        return masked_collectives.clustered_mean_gathered(
            vals, slots, n_slots, axis, n_valid=n_valid)
    return masked_collectives.clustered_weighted_mean_sharded(
        vals, slots, jnp.ones_like(slots, jnp.float32), n_slots, axis)


def _sync_round_body(strategy, axis: str, collective: str,
                     n_valid: int | None):
    """Per-shard body of one full sync round (train → masked collective
    → server_update → broadcast-apply → evaluate).  Only valid for the
    identity wire (dense float32) and strategies without a server-side
    ``assign`` hook: lossy codecs need the host codec boundary and
    dynamic assignment is its own sharded stage, both of which split
    the round into the stage programs below.  ``server`` is the
    strategy-owned :class:`~repro.fl.runtime.strategy.ServerState`
    pytree, replicated; its ``server_update`` hook (or the Alg. 2
    default) folds the collective's result in, inside the program."""
    server_update = resolve_server_update(strategy)

    def body(sub_cs, server, sub_data, keys, arrive):
        new_sub, up = _client_step_block(strategy)(
            sub_cs, server.slots, sub_data, keys)
        masked = jnp.where(arrive[:, None], up.slots, -1)
        agg, counts = _sharded_masked_mean(
            up.vecs.reshape(-1, strategy.vec_dim), masked.reshape(-1),
            strategy.n_slots, axis, collective, n_valid)
        server2 = server_update(server, agg, counts)
        applied = applied_slots(up.slots, counts, arrive)
        merged = _broadcast_apply_merge(strategy, new_sub, applied,
                                        server2.slots, sub_cs, arrive)
        acc = _evaluate_block(strategy)(
            merged, sub_data.x_test, sub_data.y_test)
        return merged, server2, counts, applied, acc, up.slots

    return body


def build_sharded_round(strategy, mesh, axis_name: str = "clients",
                        collective: str = "psum",
                        n_clients: int | None = None):
    """One full sync round as a single shard-mappable callable —
    ``(sub_cs, server_state, sub_data, keys, arrive) → (new_cs,
    server_state, counts, applied, per_client_acc, slots)`` with clients
    sharded over ``axis_name`` and the
    :class:`~repro.fl.runtime.strategy.ServerState` pytree replicated
    both ways (the strategy's ``server_update`` runs inside the
    program).  This is what the dry-run lowers on the production mesh
    (clients over the ``data`` axis) to measure the masked collective's
    bytes in the partitioned HLO, and what the :class:`ShardMapExecutor`
    runs for the identity-wire fast path.
    """
    if collective not in COLLECTIVES:
        raise ValueError(f"unknown collective {collective!r}")
    n_valid = None if n_clients is None else n_clients * strategy.j_slots
    body = _sync_round_body(strategy, axis_name, collective, n_valid)
    spec = P(axis_name)
    # check_vma=False: the varying-axes checker cannot infer that
    # all_gather→slice→einsum yields a replicated value (it does, by
    # construction — every shard reduces the same gathered array)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec, P(), spec, spec, spec),
        out_specs=(spec, P(), P(), spec, spec, spec), check_vma=False)


# ---------------------------------------------------------------------------
# stage programs (jitted once per (strategy, mesh) via static args)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnums=(0, 1, 2))
def _train_program(strategy, mesh, axis, sub_cs, server, sub_data, keys):
    spec = P(axis)

    def body(cs, srv, d, k):
        return _client_step_block(strategy)(cs, srv, d, k)

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(spec, P(), spec, spec),
                         out_specs=(spec, spec))(sub_cs, server, sub_data,
                                                 keys)


@partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5))
def _agg_program(n_slots, dim, mesh, axis, collective, n_valid,
                 dec, slots, arrive):
    spec = P(axis)

    def body(dec_, slots_, arrive_):
        masked = jnp.where(arrive_[:, None], slots_, -1)
        return _sharded_masked_mean(
            dec_.reshape(-1, dim), masked.reshape(-1), n_slots, axis,
            collective, n_valid)

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(spec, spec, spec),
                         out_specs=(P(), P()),
                         check_vma=False)(dec, slots, arrive)


@partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _assign_program(strategy, mesh, axis, k, k_padded,
                    server, dec, slots, arrive):
    """The server-side assignment stage, shard-mapped: one tiled
    ``all_gather`` per upload lane reassembles the round's decoded
    uploads in canonical client order (trimmed to the true K), every
    shard computes the *identical* replicated assignment via the
    strategy's ``assign`` hook (cross-client math — similarity graphs,
    clustering — is allowed exactly here), and each shard slices back
    its own block of the new slot ids."""
    spec = P(axis)
    n_shards = int(mesh.shape[axis])
    blk = k_padded // n_shards

    def body(server_, dec_, slots_, arrive_):
        g = lambda a: jax.lax.all_gather(a, axis, tiled=True)[:k]
        new = strategy.assign(server_, g(dec_), g(slots_), g(arrive_))
        new = new.astype(jnp.int32)
        pad = k_padded - k
        if pad:
            new = jnp.concatenate(
                [new, jnp.full((pad,) + new.shape[1:], -1, jnp.int32)])
        i = jax.lax.axis_index(axis)
        return jax.lax.dynamic_slice_in_dim(new, i * blk, blk)

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(), spec, spec, spec),
                         out_specs=spec, check_vma=False)(
        server, dec, slots, arrive)


@partial(jax.jit, static_argnums=(0, 1, 2))
def _apply_program(strategy, mesh, axis, new_sub, applied, rx_server,
                   old_sub, recv):
    spec = P(axis)

    def body(ns, ap, srv, old, rc):
        return _broadcast_apply_merge(strategy, ns, ap, srv, old, rc)

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(spec, spec, P(), spec, spec),
                         out_specs=spec)(new_sub, applied, rx_server,
                                         old_sub, recv)


@partial(jax.jit, static_argnums=(0, 1, 2))
def _eval_program(strategy, mesh, axis, cs, x_test, y_test):
    spec = P(axis)
    return jax.shard_map(
        _evaluate_block(strategy),
        mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec)(cs, x_test, y_test)


@partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _fused_program(strategy, mesh, axis, collective, n_valid,
                   sub_cs, server, sub_data, keys, arrive):
    spec = P(axis)
    body = _sync_round_body(strategy, axis, collective, n_valid)
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(spec, P(), spec, spec, spec),
                         out_specs=(spec, P(), P(), spec, spec, spec),
                         check_vma=False)(
        sub_cs, server, sub_data, keys, arrive)


# ---------------------------------------------------------------------------
# the async buffered update (device buffer, one compiled program)
# ---------------------------------------------------------------------------
#
# The buffer is six fixed-capacity lanes carried in EngineState —
# payloads (cap, d) plus slot-id / maturity-round / staleness-weight /
# validity / insertion-seq lanes (cap,).  Everything below is pure jax:
# per-shard it is *replicated* state (in_specs P()), because insertion
# is a global sequential decision every shard must agree on.

def _buffer_insert(buf, up_vecs, up_slots, up_ready, up_weight, up_valid):
    """Sequential masked insert of one round's uploads — the compiled
    form of the host insert loop, bit-identical by construction.

    Replicated per shard (no collective): a ``lax.scan`` over the U
    uploads where each step picks the first free lane
    (``argmin(valid)``), or on overflow evicts the oldest *insertion*
    (``argmin(seq over valid)``), and applies a masked single-row
    update (``up_valid=False`` rows are no-ops, so padding is inert).
    Returns ``(new_buf, evicted_count)``.
    """
    intmax = jnp.iinfo(jnp.int32).max
    vecs, slots, ready, weight, valid, seq = buf
    next_seq = jnp.where(valid.any(),
                         jnp.where(valid, seq, -1).max() + 1,
                         0).astype(jnp.int32)

    def step(carry, up):
        vecs, slots, ready, weight, valid, seq, nseq, evicted = carry
        v, s, rdy, w, ins = up
        full = valid.all()
        i_free = jnp.argmin(valid)                    # first invalid lane
        i_old = jnp.argmin(jnp.where(valid, seq, intmax))
        i = jnp.where(full, i_old, i_free)
        vecs = vecs.at[i].set(jnp.where(ins, v, vecs[i]))
        slots = slots.at[i].set(jnp.where(ins, s, slots[i]))
        ready = ready.at[i].set(jnp.where(ins, rdy, ready[i]))
        weight = weight.at[i].set(jnp.where(ins, w, weight[i]))
        seq = seq.at[i].set(jnp.where(ins, nseq, seq[i]))
        valid = valid.at[i].set(valid[i] | ins)
        evicted = evicted + (ins & full).astype(jnp.int32)
        nseq = nseq + ins.astype(jnp.int32)
        return (vecs, slots, ready, weight, valid, seq, nseq, evicted), None

    carry = (vecs, slots, ready, weight, valid, seq, next_seq,
             jnp.zeros((), jnp.int32))
    carry, _ = jax.lax.scan(
        step, carry, (up_vecs, up_slots, up_ready, up_weight, up_valid))
    vecs, slots, ready, weight, valid, seq, _, evicted = carry
    return (vecs, slots, ready, weight, valid, seq), evicted


def _async_gate_and_mean(buf, round_idx, n_slots, min_uploads, prev,
                         mean_fn):
    """Maturity gate + staleness-discounted mean, branchless.

    An entry is *mature* once ``round_idx`` reaches its ready round; it
    *contributes* if its discount weight is nonzero.  The
    ``async_min_uploads`` gate is a masked predicate: below threshold
    every slot id is masked to −1, so counts are zero, the server keeps
    ``prev`` row-for-row, and the buffer is left untouched — the same
    observable as the host engine's early return, with no host branch.
    ``mean_fn(vals, slots, weights) → (C, d)`` is the backend's
    lowering of the weighted mean (host einsum, or a mesh collective).
    Returns ``(server, counts, n_agg, n_buffered, new_buf)``.
    """
    vecs, slots, ready, weight, valid, seq = buf
    mature = valid & (ready <= round_idx)
    # zero-discount entries can never move the weighted mean — count
    # them as consumed noise, not as aggregated uploads (host parity)
    contrib = mature & (weight > 0.0)
    gate = mature.sum() >= min_uploads
    s = jnp.where(contrib & gate, slots, -1)
    w = jnp.where(contrib & gate, weight, 0.0)
    mean = mean_fn(vecs, s, w)
    counts = jax.nn.one_hot(s, n_slots, dtype=jnp.float32).sum(0)
    server = jnp.where(counts[:, None] > 0, mean, prev)
    valid = jnp.where(gate, valid & ~mature, valid)
    n_agg = jnp.where(gate, contrib.sum(), 0).astype(jnp.int32)
    new_buf = (vecs, slots, ready, weight, valid, seq)
    return server, counts, n_agg, valid.sum().astype(jnp.int32), new_buf


@partial(jax.jit, static_argnums=(0, 1))
def _async_update_program(n_slots, min_uploads, buf, up, round_idx, prev):
    """In-process async round update: insert → gate → host-form mean,
    one jitted program (no host round-trips between the stages)."""
    buf, evicted = _buffer_insert(buf, *up)
    server, counts, n_agg, n_buf, buf = _async_gate_and_mean(
        buf, round_idx, n_slots, min_uploads, prev,
        lambda v, s, w: masked_collectives.clustered_weighted_mean(
            v, s, w, n_slots))
    return server, counts, n_agg, n_buf, evicted, buf


def build_sharded_async_update(strategy, mesh, axis_name: str = "clients",
                               collective: str = "gather",
                               min_uploads: int = 4,
                               n_valid: int | None = None):
    """The async buffered update as one shard-mappable callable —
    ``(buf, (up_vecs, up_slots, up_ready, up_weight, up_valid),
    round_idx, prev) → (server, counts, n_agg, n_buf, evicted, buf)``.

    Uploads are sharded over ``axis_name`` (one block per shard, like
    the sync round); the buffer lanes, ``round_idx`` and ``prev`` are
    replicated, and so is everything returned.  Inside the body one
    tiled ``all_gather`` per upload lane reassembles the round's
    uploads in canonical client order (trimmed to ``n_valid`` to drop
    mesh padding), every shard replays the identical insert scan, and
    the mean lowers per ``collective``:

    * ``gather`` — the host-form ``clustered_weighted_mean`` on the
      replicated buffer: zero extra collectives, **bit-exact** with the
      in-process program (the conformance suite's async contract);
    * ``psum`` — :func:`masked_collectives.buffered_weighted_mean_sharded`,
      each shard reducing its block of buffer rows into the C·m psum
      accumulator (allclose, shard-order reduction).

    This is also what ``fed_dryrun`` lowers on the production mesh to
    price the async round's collectives.
    """
    if collective not in COLLECTIVES:
        raise ValueError(f"unknown collective {collective!r}")
    n_slots = strategy.n_slots
    # clients may live on one mesh axis ("clients") or a tuple of FSDP
    # axes (the dry-run's ("pod", "data")); collectives take either,
    # shard count is the product
    names = axis_name if isinstance(axis_name, tuple) else (axis_name,)
    n_shards = 1
    for a in names:
        n_shards *= int(mesh.shape[a])
    spec = P(axis_name)

    def body(buf, up, round_idx, prev):
        gathered = tuple(
            jax.lax.all_gather(a, axis_name, tiled=True)[:n_valid]
            for a in up)
        buf, evicted = _buffer_insert(buf, *gathered)
        if collective == "gather":
            def mean_fn(v, s, w):
                return masked_collectives.clustered_weighted_mean(
                    v, s, w, n_slots)
        else:
            def mean_fn(v, s, w):
                return masked_collectives.buffered_weighted_mean_sharded(
                    v, s, w, n_slots, axis_name, n_shards)[0]
        server, counts, n_agg, n_buf, buf = _async_gate_and_mean(
            buf, round_idx, n_slots, min_uploads, prev, mean_fn)
        return server, counts, n_agg, n_buf, evicted, buf

    # check_vma=False: every shard computes the same replicated insert /
    # gate from the same gathered uploads (the varying-axes checker
    # cannot see through all_gather→scan→einsum to infer that)
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(), spec, P(), P()),
                         out_specs=P(), check_vma=False)


@partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5))
def _async_sharded_program(n_slots_strategy, mesh, axis, collective,
                           min_uploads, n_valid, buf, up, round_idx, prev):
    return build_sharded_async_update(
        n_slots_strategy, mesh, axis_name=axis, collective=collective,
        min_uploads=min_uploads, n_valid=n_valid)(buf, up, round_idx, prev)


# ---------------------------------------------------------------------------
# shard_map backend
# ---------------------------------------------------------------------------

class ShardMapExecutor:
    """The production-mesh backend: every stage is a compiled shard_map
    program over ``axis`` (clients one-block-per-shard), cached across
    rounds/engines by jit's static-argument cache."""

    def __init__(self, mesh=None, axis: str = "clients",
                 collective: str = "gather"):
        if collective not in COLLECTIVES:
            raise ValueError(f"unknown collective {collective!r}")
        if mesh is None:
            from repro.launch.mesh import make_clients_mesh
            mesh = make_clients_mesh(axis=axis)
        if axis not in mesh.shape:
            raise ValueError(f"mesh has no {axis!r} axis: {mesh}")
        self.mesh = mesh
        self.axis = axis
        self.collective = collective
        self.n_shards = int(mesh.shape[axis])

    def train(self, strategy, sub_cs, server, sub_data, keys):
        k = keys.shape[0]
        new_sub, upload = _train_program(
            strategy, self.mesh, self.axis,
            _pad_tree(sub_cs, self.n_shards), server,
            _pad_tree(sub_data, self.n_shards),
            _pad_rows(keys, self.n_shards))
        new_sub = _unpad(new_sub, k)
        return new_sub, upload.vecs[:k], upload.slots[:k]

    def assign(self, strategy, server, dec, slots, arrive):
        """Shard-mapped server-side assignment: uploads sharded over
        ``axis`` (padded with inert slot-−1 / non-arrived rows), the
        server state replicated, the gathered assignment replayed
        identically on every shard — see :func:`_assign_program`."""
        k = slots.shape[0]
        k_padded = k + ((-k) % self.n_shards)
        out = _assign_program(
            strategy, self.mesh, self.axis, k, k_padded, server,
            _pad_rows(dec, self.n_shards),
            _pad_rows(slots, self.n_shards, fill=-1),
            _pad_rows(arrive, self.n_shards, fill=False))
        return out[:k]

    def masked_mean(self, strategy, dec, slots, arrive):
        k = dec.shape[0]
        return _agg_program(
            strategy.n_slots, strategy.vec_dim, self.mesh, self.axis,
            self.collective, k * strategy.j_slots,
            _pad_rows(dec, self.n_shards),
            _pad_rows(slots, self.n_shards, fill=-1),
            _pad_rows(arrive, self.n_shards, fill=False))

    def apply_merge(self, strategy, new_sub, applied, rx_server, old_sub,
                    recv):
        k = applied.shape[0]
        merged = _apply_program(
            strategy, self.mesh, self.axis,
            _pad_tree(new_sub, self.n_shards),
            _pad_rows(applied, self.n_shards, fill=-1), rx_server,
            _pad_tree(old_sub, self.n_shards),
            _pad_rows(recv, self.n_shards, fill=False))
        return _unpad(merged, k)

    def evaluate(self, strategy, cs, x_test, y_test):
        n = x_test.shape[0]
        acc = _eval_program(
            strategy, self.mesh, self.axis, _pad_tree(cs, self.n_shards),
            _pad_rows(x_test, self.n_shards),
            _pad_rows(y_test, self.n_shards))
        return acc[:n]

    def async_update(self, strategy, buf, up, round_idx, prev,
                     min_uploads: int):
        """The shard-mapped async buffered update: uploads sharded over
        ``axis`` (padded to the mesh with inert ``valid=False`` rows),
        buffer lanes / round index / server replicated, one compiled
        program per (strategy, upload-count) — see
        :func:`build_sharded_async_update`."""
        uv, us, ur, uw, ua = up
        u = uv.shape[0]
        padded = (_pad_rows(uv, self.n_shards),
                  _pad_rows(us, self.n_shards, fill=-1),
                  _pad_rows(ur, self.n_shards, fill=0),
                  _pad_rows(uw, self.n_shards, fill=0.0),
                  _pad_rows(ua, self.n_shards, fill=False))
        return _async_sharded_program(
            strategy, self.mesh, self.axis, self.collective, min_uploads,
            u, buf, padded, round_idx, prev)

    def fused_sync_round(self, strategy, sub_cs, server, sub_data, keys,
                         arrive):
        """The whole round as one compiled sharded program (identity
        wire only — the engine calls this for dense float32 sync)."""
        k = keys.shape[0]
        out = _fused_program(
            strategy, self.mesh, self.axis, self.collective,
            k * strategy.j_slots,
            _pad_tree(sub_cs, self.n_shards), server,
            _pad_tree(sub_data, self.n_shards),
            _pad_rows(keys, self.n_shards),
            _pad_rows(jnp.asarray(arrive), self.n_shards, fill=False))
        merged, server2, counts, applied, acc, slots = out
        return (_unpad(merged, k), server2, counts, applied[:k], acc[:k],
                slots[:k])
