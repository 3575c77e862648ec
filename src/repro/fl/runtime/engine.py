"""The orchestrated federated round engine.

Replaces the monolithic ``federation.run`` loop with a composition of the
scheduler (who participates), a :class:`~repro.fl.runtime.strategy.Strategy`
(what a round means), the wire codec (what actually crosses the network,
metered byte-exact), and round-granular checkpointing.

Round anatomy
-------------
One round is two halves around one row seam (``docs/architecture.md``):

1. ``scheduler.sample`` picks K-of-N clients (K static, so the round's
   programs compile once), plus dropout and straggler draws.  A sync
   barrier treats uploads that miss the deadline (staleness > 0) like
   drops.
2. **Gather** through the rows object (:mod:`repro.fl.runtime.rows`):
   resident device rows or the mmap store.  Per-client rng keys are
   ``split(round_key, N)[idx]``, so any participation pattern draws from
   the full-population key stream.
3. **Client half**: the K clients run ``strategy.client_step`` from the
   broadcast rows as they hold them, and each surviving upload is
   *encoded to real bytes* and decoded back, so lossy codecs perturb the
   math as in deployment and metering sees the client-proposed tags.
   *Where* the step runs is the executor's business
   (:mod:`repro.fl.runtime.executors`): in-process vmap, or shard-mapped
   over a ``clients`` mesh axis.  On the identity wire with the sync
   barrier and the identity gather, the shard-mapped executor runs the
   whole round as one compiled program instead (the fused shape).
4. **Server half** (:meth:`Engine.serve_uploads`, which the transport
   runner calls too): the strategy's ``assign`` hook if any, the
   per-slot masked mean folded in by ``server_update`` (default: Alg. 2
   retention), the metered broadcast of each aggregated slot row to the
   clients it came from, and the sparse wire's reference tracking.
5. **Commit** through the rows object: the merged cohort back to its
   store, evaluation, the next :class:`EngineState`; then the
   :class:`RoundReport`.

Async buffered mode
-------------------
Uploads land in a fixed-capacity buffer with masked validity instead of a
barrier; an entry matures at round ``r + staleness``.  As soon as
``async_min_uploads`` matured entries are available the engine aggregates
them with staleness-discounted weights (``discount ** staleness``) — the
FedAsync-style weighted mean — and invalidates the consumed entries.  On
overflow the oldest entry is evicted (counted in the round report).

The buffer is *device* state: six fixed-capacity lanes carried in
:class:`EngineState` (so checkpoints capture it and async resume is
bit-identical), updated by one compiled masked program per round — the
insert/evict scan and the maturity gate live in
:mod:`repro.fl.runtime.executors`, and under ``backend="shardmap"`` the
whole update runs inside ``shard_map`` on the ``clients`` mesh axis
with the staleness-discounted mean lowered through
:mod:`repro.fl.masked_collectives`.  ``async_buffer="host"`` keeps the
numpy insert (:func:`host_buffer_insert`) as the in-process reference
the conformance suite pins the device path against, bit for bit.  See
``docs/async-runtime.md`` for the lane layout and design.

Sharding contract: the engine itself never runs inside ``shard_map`` —
it holds replicated state (``server``, the buffer lanes, round index)
plus client-major arrays (``client_state``, data, per-client keys) and
hands them to the executor, which decides whether client-major means
"vmapped on one device" or "one block per mesh shard".  Everything the
engine reads back from an executor (server, counts, report scalars) is
replicated/host-visible.  On the shard-mapped backend the engine places
the population once — data at construction, client state and server at
``init`` (``ShardMapExecutor.place``) — so rounds find it on the mesh.
"""
from __future__ import annotations

import dataclasses
import inspect
import logging
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.partition import ClientData
from repro.fl import masked_collectives
from repro.fl.obs.recorder import NULL as NULL_TELEMETRY
from repro.fl.runtime import checkpointing
from repro.fl.runtime.codec import CodecConfig, decode, ef_encode, encode
from repro.fl.runtime import executors
from repro.fl.runtime.executors import (COLLECTIVES, InProcessExecutor,
                                        ShardMapExecutor)
from repro.fl.runtime.rows import Cohort, ResidentRows, StoreRows
from repro.fl.runtime.scheduler import (Participation, Scheduler,
                                        SchedulerConfig)
from repro.fl.runtime.strategy import (DOWNLOADS, ServerState,
                                       resolve_server_update)
from repro.fl.store.client_store import ClientStore

BACKENDS = ("inprocess", "shardmap")
TM_BACKENDS = ("ref", "pallas")
CLIENT_STORES = ("resident", "mmap")
STORE_EVALS = ("full", "sampled")
TRANSPORTS = ("inprocess", "loopback", "socket")


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    rounds: int = 10
    scheduler: SchedulerConfig = SchedulerConfig()
    codec: CodecConfig = CodecConfig()
    aggregation: str = "sync"         # sync | async
    async_min_uploads: int = 4        # B — aggregate once B uploads matured
    buffer_capacity: int = 64         # fixed-capacity async upload buffer
    staleness_discount: float = 0.5   # matured weight = discount**staleness
    async_buffer: str = "device"      # device (compiled) | host (reference)
    backend: str = "inprocess"        # inprocess | shardmap
    mesh_axis: str = "clients"        # shard_map axis clients live on
    mesh_collective: str = "gather"   # gather (bit-exact) | psum (C·m bytes)
    tm_backend: str = "ref"           # ref (jnp) | pallas (fused TM kernels)
    checkpoint_dir: str | None = None
    checkpoint_every: int = 0         # 0 = never
    # K-active working set over the host-side client store: "mmap" keeps
    # client rows (params, TA state, sparse-codec refs) in a
    # memory-mapped ClientStore and only the scheduler's K sampled rows
    # ever become device arrays — device/RAM footprint O(K), not O(N).
    client_store: str = "resident"    # resident | mmap
    store_dir: str | None = None      # mmap store root (None = fresh temp)
    store_eval: str = "full"          # full (chunked population) | sampled
    store_eval_chunk: int = 256       # clients per chunked-eval gather
    # real-transport runtime (repro.fl.transport): "inprocess" is this
    # engine's direct function-call wire; "loopback" runs the same round
    # protocol through in-memory length-prefixed frames (the reference
    # the conformance suite pins bit-identical to inprocess on the
    # identity wire); "socket" runs M real client-worker subprocesses
    # over local TCP, where staleness/dropout are observed arrivals.
    transport: str = "inprocess"      # inprocess | loopback | socket
    workers: int = 0                  # socket worker process count (>= 1)

    def __post_init__(self):
        if self.aggregation not in ("sync", "async"):
            raise ValueError(f"unknown aggregation {self.aggregation!r}")
        if self.transport not in TRANSPORTS:
            raise ValueError(
                f"unknown transport {self.transport!r}; choose from "
                f"{TRANSPORTS} (see docs/transport.md)")
        if self.transport != "inprocess" and self.workers < 1:
            raise ValueError(
                f"transport={self.transport!r} partitions the client "
                "population over worker peers — set workers >= 1 "
                f"(got workers={self.workers})")
        if self.transport == "inprocess" and self.workers != 0:
            raise ValueError(
                f"workers={self.workers} is a transport knob; "
                "transport='inprocess' runs no workers (leave workers=0)")
        if self.transport != "inprocess" and self.aggregation == "async" \
                and self.codec.sparse:
            raise ValueError(
                "sparse delta coding needs encoder and decoder to agree "
                "on the reference rows at decode time; the arrival-"
                "driven async transport decodes uploads rounds after "
                "they were encoded, so run sparse=True with "
                "aggregation='sync' or transport='inprocess'")
        if self.transport != "inprocess" and self.backend != "inprocess":
            raise ValueError(
                f"transport={self.transport!r} distributes clients over "
                "worker processes — it composes with backend='inprocess' "
                f"only, not backend={self.backend!r} (shard_map is "
                "single-process mesh parallelism)")
        if self.transport != "inprocess" and self.client_store != "resident":
            raise ValueError(
                f"transport={self.transport!r} requires "
                "client_store='resident': worker processes own their "
                "client rows, which contradicts the single-process mmap "
                "store")
        if self.codec.error_feedback and self.client_store != "resident":
            raise ValueError(
                "codec.error_feedback keeps per-(client, slot) residual "
                "memory in EngineState — available with "
                "client_store='resident' only (the mmap store does not "
                "carry the residual lane)")
        if self.client_store not in CLIENT_STORES:
            raise ValueError(f"unknown client_store {self.client_store!r}")
        if self.store_eval not in STORE_EVALS:
            raise ValueError(f"unknown store_eval {self.store_eval!r}")
        if self.store_eval_chunk < 1:
            raise ValueError("store_eval_chunk must be >= 1")
        if self.backend not in BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}")
        if self.tm_backend not in TM_BACKENDS:
            raise ValueError(f"unknown tm_backend {self.tm_backend!r}")
        if self.mesh_collective not in COLLECTIVES:
            raise ValueError(
                f"unknown mesh_collective {self.mesh_collective!r}")
        if self.async_buffer not in ("device", "host"):
            raise ValueError(f"unknown async_buffer {self.async_buffer!r}")
        if self.backend == "shardmap" and self.aggregation == "async" \
                and self.async_buffer == "host":
            raise ValueError(
                "the host-buffered async reference is in-process only — "
                "the shard-mapped backend runs async_buffer='device'")


class EngineState(NamedTuple):
    round_idx: jnp.ndarray      # () int32 — next round to run
    client_state: Any           # strategy pytree, leading axis = clients
    server: ServerState         # strategy-owned pytree: (n_slots, d)
    #                             slot matrix + opaque aux (probe sets,
    #                             membership tables, ...), checkpointed
    #                             as one subtree
    buf_vecs: jnp.ndarray       # (cap, d) float32   async upload buffer
    buf_slots: jnp.ndarray      # (cap,) int32       (−1 = empty)
    buf_ready: jnp.ndarray      # (cap,) int32       round the entry matures
    buf_weight: jnp.ndarray     # (cap,) float32     staleness discount
    buf_valid: jnp.ndarray      # (cap,) bool        masked validity
    buf_seq: jnp.ndarray        # (cap,) int32       insertion order
    # per-client broadcast references for the sparse-delta wire: the
    # server rows each client last *received* (zeros = never synced),
    # and the round it received them (−1 = never).  Deltas are encoded
    # and decoded against these — both endpoints know them, because the
    # aggregator tracks exactly what it sent whom — so metered savings
    # stay honest under partial participation.  Zero-size placeholders
    # when the codec is dense (no reference to track).
    ref_vecs: jnp.ndarray       # (n, n_slots, d) float32, or (0, 0, 0)
    ref_round: jnp.ndarray      # (n,) int32, or (0,)
    # error-feedback residual memory (codec.error_feedback): the
    # quantization error each client's last frame for each slot left
    # behind, added back before the next encode (compression v2).
    # Carried here so checkpoints capture it and lossy-EF resume is
    # bit-identical.  Zero-size placeholder when EF is off.
    ef_residual: jnp.ndarray  # (n, n_slots, d) float32, or (0, 0, 0)


class RoundReport(NamedTuple):
    round_idx: int
    mean_accuracy: jnp.ndarray
    per_client_accuracy: jnp.ndarray   # (n,)
    assignment: jnp.ndarray            # (n, j) int32, −1 = not shared
    cluster_counts: jnp.ndarray        # (n_slots,)
    participation: Participation
    upload_bytes: int                  # Σ len(frame) actually sent up
    download_bytes_broadcast: int      # one frame per populated slot
    download_bytes_per_client: int     # Σ over receiving participants
    aggregated_uploads: int            # uploads folded into the server
    buffered_uploads: int              # async: still waiting in the buffer
    evicted_uploads: int               # async: lost to buffer overflow
    store_read_bytes: int = 0          # mmap store host reads this round
    store_written_bytes: int = 0       # mmap store host writes this round
    # real-transport gauges (repro.fl.transport): total framed bytes the
    # server actually put on / took off the wire this round — envelopes
    # and headers included, unlike the codec-metered fields above.
    # Zero on the in-process engine (nothing crossed a process wire).
    wire_tx_bytes: int = 0             # server → clients, framed
    wire_rx_bytes: int = 0             # clients → server, framed
    # per-arrival observed staleness (arrival round − source round) of
    # the uploads the transport server took in this round; None on the
    # in-process engine (staleness there is an injected schedule)
    observed_staleness: Any = None
    # {counter: (n,) per-client value} the client step measured (the TM
    # kernel path: ``tm.type1_hash_share``); None where it measures
    # nothing.  Device arrays: only the telemetry plane reads them
    client_stats: Any = None


class Served(NamedTuple):
    """The round's server half, whichever shape ran it: what the commit,
    the next state and the report read."""
    server: ServerState
    counts: jax.Array       # (n_slots,) uploads folded into each slot
    applied: jax.Array      # (K, j) slot row each receiver applies, or −1
    down_bc: int            # download bytes, one frame per populated slot
    down_pc: int            # download bytes, summed over the receivers
    refs: tuple             # the advanced (ref_vecs, ref_round)
    buf: tuple              # the async buffer's six lanes
    n_agg: int
    n_buf: int
    n_evict: int
    merged: Any = None      # in-process: the cohort after the broadcast
    acc: Any = None         # fused: the program's per-client accuracy


def _engine_state(round_idx, cs, server, buf, refs, ef) -> EngineState:
    """The one place an :class:`EngineState` is put together."""
    return EngineState(round_idx, cs, server, *buf, *refs, ef)


def _buffer(state: EngineState) -> tuple:
    """The async buffer's six lanes, in :class:`EngineState` order."""
    return (state.buf_vecs, state.buf_slots, state.buf_ready,
            state.buf_weight, state.buf_valid, state.buf_seq)


def host_buffer_insert(buf, entries) -> tuple[tuple, int]:
    """Insert ``(vec, slot, ready, weight)`` entries, in order, into a
    host copy of the async buffer's six lanes: the first free slot, else
    evict the oldest *insertion*.  Returns the numpy lanes and the count
    evicted.  The engine's scheduled uploads (``ready = r + staleness``)
    and the transport runner's arrivals (``ready = r``) both insert
    here; the compiled device insert is pinned against it."""
    vecs, bslots, ready, weight, valid, seq = (np.array(a) for a in buf)
    evicted = 0
    next_seq = int(seq[valid].max()) + 1 if valid.any() else 0
    for vec, slot, rdy, w in entries:
        free = np.nonzero(~valid)[0]
        if free.size:
            i = free[0]
        else:       # overflow: evict the oldest *insertion*
            occupied = np.where(valid, seq, np.iinfo(np.int32).max)
            i = int(np.argmin(occupied))
            evicted += 1
        vecs[i], bslots[i], ready[i], weight[i] = vec, slot, rdy, w
        valid[i], seq[i] = True, next_seq
        next_seq += 1
    return (vecs, bslots, ready, weight, valid, seq), evicted


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class Engine:
    """Round orchestrator for one strategy over one client population."""

    def __init__(self, strategy, data: ClientData, cfg: RuntimeConfig,
                 client_weights: jnp.ndarray | None = None, mesh=None,
                 telemetry=None):
        # tm_backend="pallas" routes TM strategies through the fused
        # Pallas kernels: TMConfig.use_kernel flips the per-op dispatch
        # in core/tm.py *and* makes the strategy advertise its fused
        # client-batched hooks to the executors (strategy.py /
        # executors._client_step_block).  Non-TM strategies (no tm_cfg)
        # are untouched — the flag is a no-op for the MLP baselines.
        if cfg.tm_backend == "pallas" and \
                getattr(strategy, "tm_cfg", None) is not None:
            from repro.kernels import draws, train_epoch
            tc = strategy.tm_cfg
            need = train_epoch.vmem_bytes(tc.n_classes, tc.n_clauses,
                                          tc.n_literals)
            if need > train_epoch.VMEM_BUDGET:
                raise ValueError(
                    f"tm_backend='pallas' keeps one client's TA bank in "
                    f"VMEM: C={tc.n_classes}, m={tc.n_clauses}, "
                    f"L={tc.n_literals} needs {need} B, over the "
                    f"{train_epoch.VMEM_BUDGET} B budget — run "
                    f"tm_backend='ref' or fewer clauses")
            logging.getLogger(__name__).info(
                "tm_backend='pallas': Type-I coins hashed in the epoch "
                "kernel, one threefry word an automaton of an active "
                "Type-I row a sample (%s stream)", draws.threefry_stream())
            strategy = dataclasses.replace(
                strategy, tm_cfg=dataclasses.replace(
                    strategy.tm_cfg, use_kernel=True))
        self.strategy = strategy
        self.data = data
        self.cfg = cfg
        # population size: a streaming pool knows its client count
        # without materializing anything; ClientData carries it as the
        # leading axis of every array
        n_clients = getattr(data, "n_clients", None)
        self.n = int(n_clients) if n_clients is not None \
            else int(data.x_train.shape[0])
        streaming = hasattr(data, "gather_clients")
        if streaming and cfg.client_store != "mmap":
            raise ValueError(
                "streaming client data has no materialized population "
                "for the resident engine to index — run it with "
                "RuntimeConfig(client_store='mmap')")
        # the telemetry plane (repro.fl.obs): span/fence hooks around
        # each round stage plus the per-round event sink.  Strictly
        # read-only — it consumes reports and wall clocks, and nothing
        # it computes flows back into the round, so the conformance
        # suite pins obs-on == obs-off bit for bit.  The default NULL
        # answers every hook as a no-op (no timing, no fences).
        self.obs = telemetry if telemetry is not None else NULL_TELEMETRY
        # --- server-state API v2 contract checks -------------------------
        # downloads is a validated vocabulary, not free text: a typo used
        # to silently fall through to assigned-slot broadcast/billing
        downloads = getattr(strategy, "downloads", None)
        if downloads not in DOWNLOADS:
            raise ValueError(
                f"strategy.downloads must be one of {DOWNLOADS}, got "
                f"{downloads!r} — 'assigned' broadcasts each client its "
                f"own slot row, 'all_slots' the whole matrix (IFCA)")
        self._assign = getattr(strategy, "assign", None)
        self._server_update = resolve_server_update(strategy)
        # async × dynamic assignment: strategies with server-side hooks
        # (assign / custom server_update) aggregate on the *host* buffer
        # path, where `assign` is re-run over the matured buffer
        # contents at aggregation time — the buffer holds uploads across
        # rounds, so membership is recomputed when they are folded in,
        # not when they were sent.  The hook-less device/shardmap
        # programs hard-code the Alg. 2 fold and stay as they were.
        async_hooks = cfg.aggregation == "async" and (
            self._assign is not None
            or getattr(strategy, "server_update", None) is not None)
        if async_hooks and cfg.backend == "shardmap":
            raise ValueError(
                "async + server-side assign/server_update hooks "
                "aggregate on the in-process host buffer path — run "
                "this strategy with backend='inprocess' (the shard-"
                "mapped async program hard-codes the hook-less fold)")
        self._host_buffer = async_hooks or cfg.async_buffer == "host"
        if client_weights is None and cfg.scheduler.sampling == "weighted":
            # weighted sampling defaults to the real per-client dataset
            # sizes the partitioner recorded (clients with more data are
            # sampled more often, the FedAvg-paper convention)
            sizes = getattr(data, "sizes", None)
            if sizes is not None:
                client_weights = jnp.asarray(sizes, jnp.float32)
        self.scheduler = Scheduler(cfg.scheduler, self.n, client_weights)
        if cfg.backend == "shardmap":
            self.executor = ShardMapExecutor(
                mesh=mesh, axis=cfg.mesh_axis,
                collective=cfg.mesh_collective)
            if not streaming:
                # the population's data lives on the mesh from here on,
                # one block of clients a shard, where every round reads it
                self.data = self.executor.place(data)
        else:
            self.executor = InProcessExecutor()
        # where the client rows live, chosen once: the round gathers and
        # commits through this seam and never asks which store it is
        self.rows = (StoreRows(self) if cfg.client_store == "mmap"
                     else ResidentRows(self))
        # identity wire + sync barrier + the identity gather: the
        # executor may run the whole round (train → masked collective →
        # apply → eval) as one compiled sharded program.  Strategies
        # with a server-side assign hook always take the staged path:
        # assignment is its own sharded stage there.
        self._fusable = (cfg.aggregation == "sync" and self.rows.identity
                         and self.wire_is_identity()
                         and self._assign is None)
        # discount**staleness lookup for the async device buffer,
        # precomputed with Python double-precision pow and cast once —
        # the same double→float32 each host insert performs, so the
        # compiled path can't drift an ulp from the reference
        self._discount = jnp.asarray(np.asarray(
            [cfg.staleness_discount ** s
             for s in range(cfg.scheduler.max_staleness + 1)], np.float32))
        # (server, roundtripped rows) of the latest broadcast — reused
        # by wire_tx_server so lossy codecs roundtrip each server once
        self._tx_cache = None

    @property
    def store(self) -> ClientStore | None:
        """The mmap client store (None for resident rows)."""
        return self.rows.store

    # -- lifecycle ---------------------------------------------------------

    def strategy_init(self, key: jax.Array):
        """The strategy's ``init`` over the whole population: ``(client
        state, server)``."""
        # v2 strategies take the client data (FLIS draws its server-side
        # probe set from the confidence split); a leftover v1 signature
        # still works, and a bare matrix return is coerced to ServerState.
        # Dispatch on positional capacity, not raw parameter count — a
        # v1 `init(key, n_clients, **kw)` must not be handed `data`
        # positionally.
        kinds = [p.kind for p in
                 inspect.signature(self.strategy.init).parameters.values()]
        takes_data = (inspect.Parameter.VAR_POSITIONAL in kinds
                      or sum(k in (inspect.Parameter.POSITIONAL_ONLY,
                                   inspect.Parameter.POSITIONAL_OR_KEYWORD)
                             for k in kinds) >= 3)
        if takes_data:
            return self.strategy.init(key, self.n, self.data)
        return self.strategy.init(key, self.n)

    def init(self, key: jax.Array) -> EngineState:
        """Round 0's state: the rows object's client rows, server and
        lanes, around an empty async buffer."""
        cs, server, refs, ef = self.rows.init(key)
        cap, d = self.cfg.buffer_capacity, self.strategy.vec_dim
        buf = (jnp.zeros((cap, d), jnp.float32),
               jnp.full((cap,), -1, jnp.int32),
               jnp.zeros((cap,), jnp.int32),
               jnp.zeros((cap,), jnp.float32),
               jnp.zeros((cap,), bool),
               jnp.zeros((cap,), jnp.int32))
        return _engine_state(jnp.zeros((), jnp.int32), cs, server, buf,
                             refs, ef)

    def next_state(self, state: EngineState, served: Served, cs,
                   ef) -> EngineState:
        """The state entering the next round: the served server, buffer
        and references around the client rows ``cs`` and the
        error-feedback lane ``ef``."""
        return _engine_state(state.round_idx + 1, cs, served.server,
                             served.buf, served.refs, ef)

    def run(self, key: jax.Array, state: EngineState | None = None,
            rounds: int | None = None
            ) -> tuple[EngineState, list[RoundReport]]:
        """Run ``cfg.rounds`` rounds — or ``rounds``, e.g. the remainder
        of an interrupted run — continuing from ``state`` if given (one
        restored by :func:`checkpointing.restore`).

        The key chain (``k_init, k_rounds = split(key)``; round r uses
        ``fold_in(k_rounds, r)`` with the *absolute* round index) matches
        the legacy ``federation.run`` driver, so both fresh runs and
        checkpoint-resumed runs reproduce it exactly.
        """
        k_init, k_rounds = jax.random.split(key)
        if state is None:
            state = self.init(k_init)
        else:
            self.rows.resume(k_init)
        reports: list[RoundReport] = []
        start = int(state.round_idx)
        n_rounds = self.cfg.rounds if rounds is None else rounds
        for r in range(start, start + n_rounds):
            if self.obs.enabled:
                self.obs.begin_round(r)
            with self.obs.span("round"):
                state, rep = self.run_round(
                    state, jax.random.fold_in(k_rounds, r))
                self.obs.fence(state)
            self.obs.on_round(rep)
            reports.append(rep)
            every = self.cfg.checkpoint_every
            if self.cfg.checkpoint_dir and every and (r + 1) % every == 0:
                checkpointing.save(
                    self.cfg.checkpoint_dir, state,
                    manifest=self.obs.manifest,
                    store_manifest=self.rows.flush())
        return state, reports

    # -- one round ---------------------------------------------------------

    def run_round(self, state: EngineState, round_key: jax.Array
                  ) -> tuple[EngineState, RoundReport]:
        """schedule → gather → the fused round, or the client half then
        :meth:`serve_uploads` → commit and evaluate → report."""
        obs = self.obs            # telemetry spans/fences — no-ops when off
        r = int(state.round_idx)
        if obs.enabled:
            obs.begin_round(r)    # the round stat of the stage annotations
        io0 = self.rows.io()
        with obs.span("schedule"):
            part = self.scheduler.sample(r, round_key)
            arrive = np.asarray(part.active)
            if self.cfg.aggregation == "sync":
                arrive = arrive & (np.asarray(part.staleness) == 0)

        # (1) the sampled rows (static K) and their per-client keys
        with obs.span("gather"):
            cohort = self.rows.gather(
                state, part, jax.random.split(round_key, self.n))
            obs.fence(cohort.keys)

        fused = None
        if self._fusable:
            with obs.span("fused_round"):
                fused = self.executor.fused_sync_round(
                    self.strategy, cohort.cs, state.server, cohort.data,
                    cohort.keys, jnp.asarray(arrive))
                obs.fence(fused)
            if fused is None:
                obs.discard("fused_round")   # in-process: no fused form
        ef = state.ef_residual      # EF needs a lossy wire: never fused
        if fused is not None:
            # bytes are metered arithmetically (float32 frames are
            # bit-exact, len = 4 + 4·d — codec-pinned)
            merged, server, counts, applied, acc, slots, stats = fused
            with obs.span("downlink"):
                up_bytes = self._identity_upload_bytes(
                    np.asarray(slots), np.asarray(part.active))
                _, *down = self.wire_downlink(server.slots, counts, arrive,
                                              applied)
            served = self._served(state, slots, arrive, server, counts,
                                  applied, down,
                                  (state.ref_vecs, state.ref_round),
                                  merged=merged, acc=acc)
        else:
            # (2) the client half.  Training starts from the
            # codec-roundtripped broadcast rows — what a client actually
            # holds after a lossy downlink (identity wire: the same).
            with obs.span("broadcast_encode"):
                tx_server = self.wire_tx_server(state.server.slots)
                obs.fence(tx_server)
            with obs.span("client_step"):
                new_sub, up = self.executor.client_step(
                    self.strategy, cohort.cs, tx_server, cohort.data,
                    cohort.keys)
                vecs, slots, stats = up
                obs.fence(new_sub, vecs, slots)
            # the wire: encode → meter → decode (sparse deltas against
            # each client's tracked broadcast reference)
            with obs.span("uplink_codec"):
                dec, up_bytes, ef = self.wire_uplink(
                    state, vecs, slots, part, sub_refs=cohort.refs)
                obs.fence(dec)
            # (3) the server half; the cohort applies its broadcast
            served = self.serve_uploads(state, part, dec, slots, arrive, r,
                                        cohort=cohort, new_sub=new_sub)

        # (4) the merged rows back to their store, and evaluation
        new_state, acc, assignment = self.rows.commit(state, cohort, served,
                                                      ef)
        io1 = self.rows.io()
        obs.count(stats)          # the client step's counters, read if on
        return new_state, self.report(
            r, part, served, up_bytes, acc, assignment,
            store_read_bytes=io1[0] - io0[0],
            store_written_bytes=io1[1] - io0[1], client_stats=stats)

    def serve_uploads(self, state: EngineState, part: Participation, dec,
                      slots, arrive, r: int, *, entries=None,
                      cohort: Cohort | None = None,
                      new_sub=None) -> Served:
        """The round's server half, from the decoded uploads to the next
        server, the slots each receiver applies and the references that
        track them; the in-process round and the transport runner both
        call it.  ``arrive`` is the receiving mask (the sync barrier's
        survivors, or every active client under async).

        Sync: the strategy's ``assign`` hook if any (the proposed tags
        were already metered), the masked mean, ``server_update``.
        Async: the executor's compiled buffer update, or the host
        reference buffer — under ``async_buffer="host"``, for strategies
        with server hooks, and for a caller's own ``entries`` (the
        runner's observed arrivals).  Then the metered broadcast of each
        aggregated slot row to the clients it came from; in-process the
        ``cohort``, trained as ``new_sub``, applies it here."""
        obs, strat = self.obs, self.strategy
        recv = jnp.asarray(arrive)
        tally = None
        if self.cfg.aggregation == "sync":
            if self._assign is not None:
                with obs.span("assign"):
                    slots = self.executor.assign(strat, state.server, dec,
                                                 slots, recv)
                    obs.fence(slots)
            with obs.span("aggregate"):
                agg, counts = self.executor.masked_mean(strat, dec, slots,
                                                        recv)
                obs.fence(agg, counts)
            with obs.span("server_update"):
                server = self._server_update(state.server, agg, counts)
                obs.fence(server)
        else:
            with obs.span("aggregate"):
                if entries is None and not self._host_buffer:
                    server, counts, tally = self._aggregate_async(
                        state, dec, slots, part)
                else:
                    if entries is None:
                        entries = self._scheduled_entries(dec, slots, part,
                                                          r)
                    server, counts, tally = self._aggregate_host(
                        state, entries, r)
                obs.fence(server, counts)

        with obs.span("downlink"):
            applied = executors.applied_slots(slots, counts, recv)
            rx_server, *down = self.wire_downlink(server.slots, counts,
                                                  arrive, applied)
            obs.fence(rx_server)
        merged = None
        if new_sub is not None:
            with obs.span("apply_merge"):
                merged = self.executor.apply_merge(
                    strat, new_sub, applied, rx_server, cohort.cs, recv)
                obs.fence(merged)
        with obs.span("ref_track"):
            refs = self.rows.track_refs(
                state, part.idx, None if cohort is None else cohort.refs,
                arrive, applied, rx_server, r)
            obs.fence(refs)
        return self._served(state, slots, arrive, server, counts, applied,
                            down, refs, tally, merged=merged)

    def _served(self, state, slots, arrive, server, counts, applied, down,
                refs, tally=None, merged=None, acc=None) -> Served:
        """Close the server half — staged or fused — into one
        :class:`Served`.  Without an async ``tally`` the round had the
        sync barrier: the uploads it let through were folded in, and
        the buffer passes on untouched."""
        if tally is None:
            tally = (_buffer(state),
                     int((np.asarray(slots)[arrive] >= 0).sum()), 0, 0)
        return Served(server, counts, applied, *down, refs, *tally,
                      merged, acc)

    def report(self, r: int, part: Participation, served: Served,
               up_bytes: int, acc, assignment, **gauges) -> RoundReport:
        """The round's report, for the in-process round and the transport
        runner alike; ``gauges`` are the store's or the wire's counters."""
        return RoundReport(
            round_idx=r, mean_accuracy=acc.mean(),
            per_client_accuracy=acc, assignment=assignment,
            cluster_counts=served.counts, participation=part,
            upload_bytes=up_bytes, download_bytes_broadcast=served.down_bc,
            download_bytes_per_client=served.down_pc,
            aggregated_uploads=served.n_agg, buffered_uploads=served.n_buf,
            evicted_uploads=served.n_evict, **gauges)

    # -- pieces ------------------------------------------------------------

    def wire_is_identity(self) -> bool:
        """Dense float32 encode→decode is a bit-exact identity (pinned by
        the codec tests) — the round needs no host codec boundary."""
        return self.cfg.codec.name == "float32" and not self.cfg.codec.sparse

    def collective_payload_bytes(self) -> int | None:
        """Per-device payload of this engine's aggregation collective on
        the mesh — the static telemetry gauge recorded in the run
        manifest (None in-process: aggregation is a local einsum)."""
        if self.cfg.backend != "shardmap":
            return None
        return masked_collectives.collective_payload_bytes(
            self.cfg.mesh_collective,
            self.scheduler.k * self.strategy.j_slots,
            self.strategy.vec_dim, self.strategy.n_slots)

    def _identity_upload_bytes(self, np_slots, active) -> int:
        """Identity-wire metering: frame = 4-byte slot id + 4·d payload,
        one frame per shared slot of each active client.  The one
        formula both the fused path and :meth:`wire_uplink`'s fast path
        meter with."""
        d = self.strategy.vec_dim
        return int((np_slots[active] >= 0).sum()) * (4 + 4 * d)

    def wire_uplink(self, state: EngineState, vecs, slots,
                    part: Participation, sub_refs=None):
        """Encode every surviving upload to real bytes; decode what the
        aggregator would see.  Frame = slot id (<i4) + encoded vector.
        Slot −1 ("nothing shared", e.g. below ``conf_threshold``) sends
        no frame, so selective sharing really does cut metered bytes.

        Sparse-delta mode encodes against the *per-client tracked
        reference* — the slot row this client last received over the
        broadcast (``state.ref_vecs``; zeros if it never synced), which
        the aggregator knows because it recorded what it sent.  A client
        that missed recent broadcasts therefore pays for its real,
        larger delta: the metered savings are honest under partial
        participation.

        Error-feedback codecs (compression v2) add each client's
        per-slot residual memory before encoding and keep this frame's
        quantization error as the next residual
        (:func:`repro.fl.runtime.codec.ef_encode`); the updated
        ``ef_residual`` lane is returned alongside the decoded uploads.
        Residuals advance for every *sent* frame — a straggler's frame
        that misses the sync barrier was still sent, so its residual
        moved."""
        cfg = self.cfg.codec
        np_slots = np.asarray(slots)
        active = np.asarray(part.active)
        if self.wire_is_identity():
            # bit-exact identity wire: skip the host round-trip, meter
            # arithmetically.  Keeps the default round free of
            # per-frame Python.
            return (vecs, self._identity_upload_bytes(np_slots, active),
                    state.ef_residual)
        np_vecs = np.asarray(vecs, np.float32)
        # gather the K participants' reference rows on device — never
        # pull the full (n, n_slots, d) population tensor to the host.
        # The mmap engine hands the store-gathered rows in directly
        # (its state lanes are zero-row placeholders).
        if not cfg.sparse:
            np_refs = None
        elif sub_refs is not None:
            np_refs = np.asarray(sub_refs[0], np.float32)
        else:
            np_refs = np.asarray(state.ref_vecs[jnp.asarray(part.idx)],
                                 np.float32)
        sub_ef = None
        if cfg.error_feedback:
            sub_ef = np.array(np.asarray(
                state.ef_residual[jnp.asarray(part.idx)], np.float32))
        dec = np.zeros_like(np_vecs)
        total = 0
        for c in range(np_vecs.shape[0]):
            if not active[c]:
                continue                    # lost mid-round: nothing sent
            for j in range(np_vecs.shape[1]):
                s = int(np_slots[c, j])
                if s < 0:
                    continue                # nothing shared in this slot
                ref = np_refs[c, s] if cfg.sparse else None
                if sub_ef is not None:
                    frame, sub_ef[c, s] = ef_encode(
                        np_vecs[c, j], cfg, sub_ef[c, s], ref=ref)
                else:
                    frame = encode(np_vecs[c, j], cfg, ref=ref)
                total += 4 + len(frame)
                dec[c, j] = decode(frame, np_vecs.shape[2], cfg, ref=ref)
        ef = state.ef_residual
        if sub_ef is not None:
            ef = ef.at[jnp.asarray(part.idx)].set(jnp.asarray(sub_ef))
        return jnp.asarray(dec), total, ef

    def row_frames(self, server) -> list[bytes]:
        """The server matrix as dense codec frames (delta coding is
        upload-only): what any broadcast of it carries over a real
        wire.  Deterministic, so both ends of a wire agree on them."""
        np_server = np.asarray(server, np.float32)
        if self.wire_is_identity():
            return [row.tobytes() for row in np_server]
        dense = CodecConfig(self.cfg.codec.name, sparse=False)
        return [encode(row, dense) for row in np_server]

    def _roundtrip_rows(self, server):
        """Every server row through the dense wire codec and back — what
        any receiver of a broadcast actually holds.  Returns ``(rx_rows,
        frame_lengths)``; float32 is a bit-exact identity, so it skips
        the host round-trip and meters arithmetically (frame = 4·d
        bytes, codec-pinned)."""
        n_rows, d = int(server.shape[0]), int(server.shape[1])
        if self.cfg.codec.name == "float32":
            return server, [4 * d] * n_rows
        dense = CodecConfig(self.cfg.codec.name, sparse=False)
        frames = self.row_frames(server)
        rx = np.asarray([decode(f, d, dense) for f in frames], np.float32)
        return jnp.asarray(rx), [len(f) for f in frames]

    def wire_tx_server(self, server):
        """The server matrix as the *clients* hold it: every row
        roundtripped through the dense codec, because the rows a client
        trains from arrived over last round's (possibly lossy)
        broadcast.  Metering is unaffected — download bytes are billed
        by :meth:`wire_downlink` when the rows are pushed; this only
        stops ``client_step`` reading precision the wire never carried
        (see docs/async-runtime.md, byte metering).

        ``state.server`` entering round r+1 is the very array
        :meth:`wire_downlink` roundtripped at the end of round r, so
        the downlink's result is cached by identity and the host
        encode/decode loop runs once per server matrix, not twice."""
        if self.wire_is_identity():
            return server
        cached = self._tx_cache
        if cached is not None and cached[0] is server:
            return cached[1]
        rx, _ = self._roundtrip_rows(server)
        self._tx_cache = (server, rx)
        return rx

    def wire_downlink(self, server, counts, arrive, applied):
        """Run the broadcast through the wire too: every slot row is
        encoded (dense — delta coding is upload-only), metered, and
        decoded, and it is the *decoded* rows clients apply — a lossy
        codec degrades the downlink exactly as it would in deployment.
        ``down_bc`` is one frame per populated slot; ``down_pc`` is the
        per-client accounting over the frames receiving participants
        actually apply (legacy §6.7 accounting)."""
        np_counts = np.asarray(counts)
        rx_arr, frame_len = self._roundtrip_rows(server)
        if not self.wire_is_identity():
            self._tx_cache = (server, rx_arr)   # next round trains from it
        down_bc = sum(frame_len[s] for s in range(len(frame_len))
                      if np_counts[s] > 0)
        if self.strategy.downloads == "all_slots":
            down_pc = int(arrive.sum()) * sum(frame_len)
        else:
            down_pc = sum(frame_len[s]
                          for s in np.asarray(applied).ravel() if s >= 0)
        return rx_arr, down_bc, down_pc

    def _aggregate_async(self, state, dec, slots, part: Participation):
        """Device-buffered aggregation (the production path): flatten
        this round's uploads into lanes — payload, slot id, maturity
        round ``r + staleness``, ``discount**staleness`` weight,
        validity — and hand them with the carried buffer to the
        executor's one compiled insert→gate→mean program.  In-process
        that is a single jitted update; shard-mapped the uploads stay
        sharded on the mesh axis and the mean is a masked collective.
        Bit-identical to :meth:`_aggregate_host`, pinned by the
        conformance suite.  Returns ``(server, counts, (buf, n_agg,
        n_buf, n_evict))``."""
        k, j = slots.shape
        active = jnp.asarray(part.active)
        stale = jnp.asarray(part.staleness, jnp.int32)
        flat = lambda a: jnp.broadcast_to(a[:, None], (k, j)).reshape(-1)
        up = (dec.reshape(k * j, -1).astype(jnp.float32),
              slots.reshape(-1).astype(jnp.int32),
              state.round_idx + flat(stale),
              self._discount[flat(stale)],
              flat(active) & (slots.reshape(-1) >= 0))
        srv, counts, n_agg, n_buf, n_evict, buf = \
            self.executor.async_update(
                self.strategy, _buffer(state), up, state.round_idx,
                state.server.slots, self.cfg.async_min_uploads)
        return (state.server._replace(slots=srv), counts,
                (buf, int(n_agg), int(n_buf), int(n_evict)))

    def _scheduled_entries(self, dec, slots, part: Participation, r: int):
        """This round's uploads as host-buffer entries under the injected
        schedule: each matures at ``r + staleness``, weighted
        ``discount**staleness``."""
        np_dec, np_slots = np.asarray(dec), np.asarray(slots)
        active, stale = np.asarray(part.active), np.asarray(part.staleness)
        disc = self.cfg.staleness_discount
        return [(np_dec[c, j], np_slots[c, j], r + int(stale[c]),
                 disc ** int(stale[c]))
                for c in range(np_dec.shape[0]) if active[c]
                for j in range(np_dec.shape[1]) if np_slots[c, j] >= 0]

    def _aggregate_host(self, state, entries, r: int):
        """Host-buffered aggregation — the numpy reference the device
        path is pinned against: insert ``entries``
        (:func:`host_buffer_insert`), then fold in every matured entry
        once ``async_min_uploads`` are available.

        Strategies with an ``assign`` hook have it re-run here over the
        matured buffer contents *at aggregation time* (buffer rows as
        single-upload clients, contribution mask as arrival), so
        FLIS-style dynamic membership is recomputed from what is
        actually being folded in — not from stale send-time tags.  The
        fold then goes through the strategy's ``server_update`` (the
        Alg. 2 default reproduces the legacy in-place write bit for
        bit).  Returns ``(server, counts, (buf, n_agg, n_buf,
        n_evict))``."""
        (vecs, bslots, ready, weight, valid, seq), n_evict = \
            host_buffer_insert(_buffer(state), entries)
        # an entry whose staleness discount rounds to zero weight can never
        # contribute to the weighted mean — treat it as consumed noise so
        # its slot isn't wrongly marked populated (and then broadcast)
        mature = valid & (ready <= r)
        contrib = mature & (weight > 0.0)
        if int(mature.sum()) >= self.cfg.async_min_uploads:
            w = jnp.asarray(np.where(contrib, weight, 0.0), jnp.float32)
            s = jnp.asarray(np.where(contrib, bslots, -1), jnp.int32)
            if self._assign is not None:
                # assignment at aggregation time: the matured buffer
                # rows are the round's "uploads" (one slot each), the
                # contribution mask the arrival vector
                new_s = self.executor.assign(
                    self.strategy, state.server,
                    jnp.asarray(vecs)[:, None, :], s[:, None],
                    jnp.asarray(contrib))
                s = jnp.where(jnp.asarray(contrib),
                              new_s[:, 0], -1).astype(jnp.int32)
            mean = masked_collectives.clustered_weighted_mean(
                jnp.asarray(vecs), s, w, self.strategy.n_slots)
            counts = jax.nn.one_hot(
                s, self.strategy.n_slots, dtype=jnp.float32).sum(0)
            server = self._server_update(state.server, mean, counts)
            valid = valid & ~mature
            n_agg = int(contrib.sum())
        else:
            server = state.server
            counts = jnp.zeros((self.strategy.n_slots,), jnp.float32)
            n_agg = 0
        buf = tuple(jnp.asarray(a)
                    for a in (vecs, bslots, ready, weight, valid, seq))
        return server, counts, (buf, n_agg, int(valid.sum()), n_evict)
