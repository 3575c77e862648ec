"""The orchestrated federated round engine.

Replaces the monolithic ``federation.run`` loop with a composition of the
scheduler (who participates), a :class:`~repro.fl.runtime.strategy.Strategy`
(what a round means), the wire codec (what actually crosses the network,
metered byte-exact), and round-granular checkpointing.

Round anatomy (sync mode)
-------------------------
1. ``scheduler.sample`` picks K-of-N clients (K static → the gather of the
   sampled client sub-pytree keeps the round a single compiled program),
   plus dropout and straggler draws.
2. The K clients run ``strategy.client_step`` (vmapped).  Per-client rng
   keys are ``split(round_key, N)[idx]``, so any participation pattern
   draws from the same per-client key stream as the full-population
   legacy loop — full participation reproduces it bit-for-bit.
   *Where* the step runs is the executor's business
   (:mod:`repro.fl.runtime.executors`): in-process vmap (default), or
   shard-mapped over a ``clients`` mesh axis (``backend="shardmap"``)
   with aggregation lowered to a single masked collective — one
   compiled sharded program per round on the identity wire.  The
   conformance suite pins both backends bit-identical.
3. Each surviving upload is *encoded to real bytes* by the codec (and
   decoded back before aggregation, so lossy codecs perturb the math
   exactly as they would in deployment).  A sync barrier treats uploads
   that miss the deadline (staleness > 0) like drops.
4. **Server-side assignment** (server-state API v2): if the strategy
   defines an ``assign`` hook, the slot id of every decoded upload is
   recomputed here — FLIS derives cluster membership per round from
   inference similarity on its probe set.  Metering (step 3) always
   uses the *client-proposed* tags: what crossed the wire crossed the
   wire.  Strategies without the hook keep their proposed ids.
5. Per-slot masked mean aggregation (slot −1 contributes nothing),
   folded into the strategy-owned :class:`ServerState` by its
   ``server_update`` hook — the default keeps empty slots' previous
   rows bit-for-bit, per Alg. 2.
6. Broadcast: each surviving participant applies its slot's new server
   row; dropped/straggling clients keep their pre-round state.  Download
   bytes are metered from the encoded broadcast frames.

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
original numpy insert loop as the in-process reference the conformance
suite pins the device path against, bit for bit.  See
``docs/async-runtime.md`` for the lane layout and design.

Sharding contract: the engine itself never runs inside ``shard_map`` —
it holds replicated state (``server``, the buffer lanes, round index)
plus client-major arrays (``client_state``, data, per-client keys) and
hands them to the executor, which decides whether client-major means
"vmapped on one device" or "one block per mesh shard".  Everything the
engine reads back from an executor (server, counts, report scalars) is
replicated/host-visible.
"""
from __future__ import annotations

import dataclasses
import inspect
import tempfile
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
from repro.fl.runtime.scheduler import (Participation, Scheduler,
                                        SchedulerConfig)
from repro.fl.runtime.strategy import (DOWNLOADS, ServerState,
                                       ensure_server_state,
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
            from repro.kernels import train_epoch
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
        self._mmap = cfg.client_store == "mmap"
        self._streaming = hasattr(data, "gather_clients")
        self.store: ClientStore | None = None
        if self._streaming and not self._mmap:
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
        self._async_hooks = cfg.aggregation == "async" and (
            self._assign is not None
            or getattr(strategy, "server_update", None) is not None)
        if self._async_hooks and cfg.backend == "shardmap":
            raise ValueError(
                "async + server-side assign/server_update hooks "
                "aggregate on the in-process host buffer path — run "
                "this strategy with backend='inprocess' (the shard-"
                "mapped async program hard-codes the hook-less fold)")
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
        else:
            self.executor = InProcessExecutor()
        # uniform full participation samples idx = arange(N): skip the
        # identity gather/scatter so the legacy-default path copies
        # nothing (the dominant configuration for every benchmark).
        # The mmap store always stages through gather/spill — its whole
        # point is that the population is never resident.
        self._identity = (self.scheduler.k == self.n
                          and cfg.scheduler.sampling == "uniform"
                          and not self._mmap)
        # discount**staleness lookup for the async device buffer,
        # precomputed with Python double-precision pow and cast once —
        # the same double→float32 each host insert performs, so the
        # compiled path can't drift an ulp from the reference
        self._discount = jnp.asarray(np.asarray(
            [cfg.staleness_discount ** s
             for s in range(cfg.scheduler.max_staleness + 1)], np.float32))
        # (server, roundtripped rows) of the latest broadcast — reused
        # by _wire_tx_server so lossy codecs roundtrip each server once
        self._tx_cache = None

    # -- lifecycle ---------------------------------------------------------

    def _full_init(self, key: jax.Array):
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
        if self._mmap:
            return self._init_mmap(key)
        cs, server = self._full_init(key)
        server = ensure_server_state(server)
        cap, d = self.cfg.buffer_capacity, self.strategy.vec_dim
        if self.cfg.codec.sparse:
            ref_vecs = jnp.zeros((self.n, self.strategy.n_slots, d),
                                 jnp.float32)
            ref_round = jnp.full((self.n,), -1, jnp.int32)
        else:
            ref_vecs = jnp.zeros((0, 0, 0), jnp.float32)
            ref_round = jnp.zeros((0,), jnp.int32)
        if self.cfg.codec.error_feedback:
            ef = jnp.zeros((self.n, self.strategy.n_slots, d), jnp.float32)
        else:
            ef = jnp.zeros((0, 0, 0), jnp.float32)
        return EngineState(
            round_idx=jnp.zeros((), jnp.int32),
            client_state=cs, server=server,
            buf_vecs=jnp.zeros((cap, d), jnp.float32),
            buf_slots=jnp.full((cap,), -1, jnp.int32),
            buf_ready=jnp.zeros((cap,), jnp.int32),
            buf_weight=jnp.zeros((cap,), jnp.float32),
            buf_valid=jnp.zeros((cap,), bool),
            buf_seq=jnp.zeros((cap,), jnp.int32),
            ref_vecs=ref_vecs, ref_round=ref_round, ef_residual=ef)

    def _init_mmap(self, key: jax.Array) -> EngineState:
        """Open the client store and return an O(K) engine state: the
        population's rows live under ``cfg.store_dir``; the returned
        state carries zero-row placeholders for ``client_state`` and
        the sparse-codec ref lanes (they, too, live in the store).

        Strategies exposing the O(K) init hooks (``init_cohort(key,
        ids, n) == init(key, n)[0][ids]`` bit-for-bit, plus
        ``init_server``) never materialize the population at all —
        unwritten store rows are regenerated per sampled cohort.
        Hookless strategies fall back to one full ``init`` whose rows
        are served by index: O(N) host RAM once, still O(K) device per
        round."""
        strat = self.strategy
        cohort = getattr(strat, "init_cohort", None)
        init_server = getattr(strat, "init_server", None)
        if cohort is not None and init_server is not None:
            server = ensure_server_state(init_server(key, self.n))
            row = jax.tree.map(lambda a: np.asarray(a)[0],
                               cohort(key, np.asarray([0]), self.n))

            def cs_init(ids):
                return jax.tree.map(
                    np.asarray, cohort(key, np.asarray(ids), self.n))
        else:
            cs, server = self._full_init(key)
            server = ensure_server_state(server)
            rows = jax.tree.map(np.asarray, cs)
            row = jax.tree.map(lambda a: a[0], rows)

            def cs_init(ids):
                np_ids = np.asarray(ids)
                return jax.tree.map(lambda a: a[np_ids], rows)

        cap, d = self.cfg.buffer_capacity, strat.vec_dim
        sparse = self.cfg.codec.sparse
        template = {"cs": row}
        if sparse:
            # the per-client broadcast references ride in the store too:
            # a never-synced client's reference is zeros / round −1,
            # exactly the resident init
            template["ref_vecs"] = np.zeros((strat.n_slots, d), np.float32)
            template["ref_round"] = np.asarray(-1, np.int32)

        def init_fn(ids):
            np_ids = np.asarray(ids)
            out = {"cs": cs_init(np_ids)}
            if sparse:
                out["ref_vecs"] = np.zeros(
                    (np_ids.size, strat.n_slots, d), np.float32)
                out["ref_round"] = np.full((np_ids.size,), -1, np.int32)
            return out

        root = self.cfg.store_dir or tempfile.mkdtemp(
            prefix="client_store_")
        self.store = ClientStore(root, self.n, template, init_fn=init_fn)
        placeholder = jax.tree.map(
            lambda a: jnp.zeros((0,) + np.asarray(a).shape,
                                np.asarray(a).dtype), row)
        return EngineState(
            round_idx=jnp.zeros((), jnp.int32),
            client_state=placeholder, server=server,
            buf_vecs=jnp.zeros((cap, d), jnp.float32),
            buf_slots=jnp.full((cap,), -1, jnp.int32),
            buf_ready=jnp.zeros((cap,), jnp.int32),
            buf_weight=jnp.zeros((cap,), jnp.float32),
            buf_valid=jnp.zeros((cap,), bool),
            buf_seq=jnp.zeros((cap,), jnp.int32),
            ref_vecs=jnp.zeros((0, 0, 0), jnp.float32),
            ref_round=jnp.zeros((0,), jnp.int32),
            ef_residual=jnp.zeros((0, 0, 0), jnp.float32))

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
        elif self._mmap:
            # resuming over an existing store: (re)open it keyed by THIS
            # run's k_init, so rows never sampled before the checkpoint
            # fault in exactly as the uninterrupted run would have
            # generated them (the `like` state a caller built for
            # checkpointing.restore may have used a different key)
            self.init(k_init)
        reports: list[RoundReport] = []
        start = int(state.round_idx)
        n_rounds = self.cfg.rounds if rounds is None else rounds
        for r in range(start, start + n_rounds):
            with self.obs.span("round"):
                state, rep = self.run_round(
                    state, jax.random.fold_in(k_rounds, r))
                self.obs.fence(state)
            self.obs.on_round(rep)
            reports.append(rep)
            every = self.cfg.checkpoint_every
            if self.cfg.checkpoint_dir and every and (r + 1) % every == 0:
                if self._mmap:
                    # the checkpoint is only the replicated state — the
                    # population rows ARE the store, flushed alongside
                    # so checkpoint + store dir resume together (valid
                    # at the latest checkpoint: store rows advance past
                    # older ones; see docs/client-store.md)
                    self.store.flush()
                checkpointing.save(
                    self.cfg.checkpoint_dir, state,
                    manifest=self.obs.manifest,
                    store_manifest=(self.store.manifest
                                    if self._mmap else None))
        return state, reports

    # -- one round ---------------------------------------------------------

    def run_round(self, state: EngineState, round_key: jax.Array
                  ) -> tuple[EngineState, RoundReport]:
        obs = self.obs            # telemetry spans/fences — no-ops when off
        r = int(state.round_idx)
        store = self.store
        if self._mmap:
            io0 = (store.io_read_bytes, store.io_written_bytes)
        with obs.span("schedule"):
            part = self.scheduler.sample(r, round_key)
            sync = self.cfg.aggregation == "sync"
            arrive = np.asarray(part.active)
            if sync:
                arrive = arrive & (np.asarray(part.staleness) == 0)

        # gather the sampled sub-pytree (static K) + per-client keys
        sub_refs = None
        with obs.span("gather"):
            keys = jax.random.split(round_key, self.n)
            if self._identity:
                sub_cs, sub_data = state.client_state, self.data
            elif self._mmap:
                # the K sampled rows come off the host store (digest-
                # verified; never-spilled rows regenerated by the
                # strategy's deterministic init) — same per-client keys
                # as the resident gather, so the round is bit-identical
                np_ids = np.asarray(part.idx)
                keys = keys[part.idx]
                bundle = store.gather(np_ids)
                sub_cs = jax.tree.map(jnp.asarray, bundle["cs"])
                if self.cfg.codec.sparse:
                    sub_refs = (jnp.asarray(bundle["ref_vecs"]),
                                jnp.asarray(bundle["ref_round"]))
                sub_data = (self.data.gather_clients(np_ids)
                            if self._streaming else
                            jax.tree.map(lambda a: a[part.idx], self.data))
            else:
                keys = keys[part.idx]
                sub_cs = jax.tree.map(lambda a: a[part.idx],
                                      state.client_state)
                sub_data = jax.tree.map(lambda a: a[part.idx], self.data)
            obs.fence(keys)

        # identity wire + sync barrier: the executor may run the whole
        # round (train → masked collective → apply → eval) as one
        # compiled sharded program; bytes are metered arithmetically
        # (float32 frames are bit-exact, len = 4 + 4·d — codec-pinned).
        # Strategies with a server-side assign hook always take the
        # staged path: assignment is its own sharded stage there.
        fused = None
        if sync and self._identity and self._wire_is_identity() \
                and self._assign is None:
            with obs.span("fused_round"):
                fused = self.executor.fused_sync_round(
                    self.strategy, sub_cs, state.server, sub_data, keys,
                    jnp.asarray(arrive))
                obs.fence(fused)
            if fused is None:
                obs.discard("fused_round")   # in-process: no fused form
        refs = (state.ref_vecs, state.ref_round)
        ef = state.ef_residual      # EF needs a lossy wire: never fused
        if fused is not None:
            merged, server, counts, applied, acc_sub, slots = fused
            with obs.span("downlink"):
                up_bytes = self._identity_upload_bytes(
                    np.asarray(slots), np.asarray(part.active))
                _, down_bc, down_pc = self._wire_downlink(
                    server.slots, counts, arrive, applied)
        else:
            # (2) local work on the K sampled clients.  Training starts
            # from the codec-roundtripped broadcast rows — what a client
            # actually holds after a lossy downlink — not the
            # aggregator's full-precision state (identity wire: same
            # thing, zero cost).
            with obs.span("broadcast_encode"):
                tx_server = self._wire_tx_server(state.server.slots)
                obs.fence(tx_server)
            with obs.span("client_step"):
                new_sub, vecs, slots = self.executor.train(
                    self.strategy, sub_cs, tx_server, sub_data, keys)
                obs.fence(new_sub, vecs, slots)

            # (3) the wire: encode → meter → decode (sparse deltas run
            # against each client's tracked broadcast reference).
            # Metering sees the client-proposed slot tags — the frames
            # that crossed the wire — never the post-assign ids.
            with obs.span("uplink_codec"):
                dec, up_bytes, ef = self._wire_uplink(
                    state, vecs, slots, part, sub_refs=sub_refs)
                obs.fence(dec)

            # (3b) server-side assignment (v2): recompute every upload's
            # slot id from the decoded payloads — FLIS's per-round
            # dynamic clustering; absent hook = keep proposed ids.
            # Async strategies skip this stage: their uploads cross
            # rounds in the buffer, so `assign` runs over the *matured
            # buffer contents* at aggregation time instead
            # (:meth:`_aggregate_async_host`).
            if self._assign is not None and sync:
                with obs.span("assign"):
                    slots = self.executor.assign(
                        self.strategy, state.server, dec, slots,
                        jnp.asarray(arrive))
                    obs.fence(slots)

            # (4) aggregation, folded into the strategy-owned server
            # state by its server_update hook (default: Alg. 2
            # retention — empty slots keep their previous row)
            if sync:
                with obs.span("aggregate"):
                    agg, counts = self.executor.masked_mean(
                        self.strategy, dec, slots, jnp.asarray(arrive))
                    obs.fence(agg, counts)
                with obs.span("server_update"):
                    server = self._server_update(state.server, agg, counts)
                    obs.fence(server)
            elif self.cfg.async_buffer == "host" or self._async_hooks:
                with obs.span("aggregate"):
                    server, counts, n_agg, n_buf, n_evict, buf = \
                        self._aggregate_async_host(state, dec, slots,
                                                   part, r)
                    obs.fence(server, counts)
            else:
                with obs.span("aggregate"):
                    srv_mat, counts, n_agg, n_buf, n_evict, buf = \
                        self._aggregate_async(state, dec, slots, part)
                    server = state.server._replace(slots=srv_mat)
                    obs.fence(server, counts)

            # (5) broadcast + scatter + evaluate.  A slot row is only
            # pushed to clients when it actually received an aggregate
            # this round — otherwise (async round below the B threshold,
            # or a never-fed cluster) the zero-initialized/stale server
            # row would overwrite the client's freshly trained weights.
            recv = jnp.asarray(arrive)
            with obs.span("downlink"):
                applied = executors.applied_slots(slots, counts, recv)
                rx_server, down_bc, down_pc = self._wire_downlink(
                    server.slots, counts, arrive, applied)
                obs.fence(rx_server)
            with obs.span("apply_merge"):
                merged = self.executor.apply_merge(
                    self.strategy, new_sub, applied, rx_server, sub_cs,
                    recv)
                obs.fence(merged)
            acc_sub = None
            with obs.span("ref_track"):
                if self._mmap:
                    if self.cfg.codec.sparse:
                        sub_ref_vecs = np.array(
                            np.asarray(sub_refs[0], np.float32))
                        sub_ref_rounds = np.array(np.asarray(sub_refs[1]))
                        self._advance_ref_rows(
                            sub_ref_vecs, sub_ref_rounds, arrive, applied,
                            rx_server, r, self.strategy.downloads)
                        sub_refs = (jnp.asarray(sub_ref_vecs),
                                    jnp.asarray(sub_ref_rounds))
                    refs = (state.ref_vecs, state.ref_round)  # placeholders
                else:
                    refs = self._update_refs(state, part, arrive, applied,
                                             rx_server, r)
                obs.fence(refs)

            # spill the merged working set (and its advanced broadcast
            # references) back to the host store — after this the round
            # holds no per-client device state beyond the K rows
            if self._mmap:
                with obs.span("spill"):
                    bundle = {"cs": jax.tree.map(np.asarray, merged)}
                    if self.cfg.codec.sparse:
                        bundle["ref_vecs"] = np.asarray(sub_refs[0],
                                                        np.float32)
                        bundle["ref_round"] = np.asarray(sub_refs[1],
                                                         np.int32)
                    store.spill(np_ids, bundle)

        if sync:   # barrier bookkeeping, identical for fused and staged
            n_agg = int((np.asarray(slots)[arrive] >= 0).sum())
            buf = self._buf_of(state)
            n_buf = n_evict = 0

        with obs.span("eval"):
            if self._mmap:
                new_state, acc, assignment = self._store_eval(
                    state, part.idx, merged, applied, server, buf, refs,
                    ef, sub_data)
            else:
                new_state, acc, assignment = self._scatter_eval(
                    state, part.idx, merged, applied, server, buf, refs,
                    ef, acc_sub)
            obs.fence(acc)

        if self._mmap:
            store_read = store.io_read_bytes - io0[0]
            store_written = store.io_written_bytes - io0[1]
        else:
            store_read = store_written = 0
        rep = RoundReport(
            round_idx=r, mean_accuracy=acc.mean(),
            per_client_accuracy=acc, assignment=assignment,
            cluster_counts=counts, participation=part,
            upload_bytes=up_bytes, download_bytes_broadcast=down_bc,
            download_bytes_per_client=down_pc, aggregated_uploads=n_agg,
            buffered_uploads=n_buf, evicted_uploads=n_evict,
            store_read_bytes=store_read, store_written_bytes=store_written)
        return new_state, rep

    # -- pieces ------------------------------------------------------------

    def _wire_is_identity(self) -> bool:
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
        formula both the fused path and ``_wire_uplink``'s fast path
        meter with."""
        d = self.strategy.vec_dim
        return int((np_slots[active] >= 0).sum()) * (4 + 4 * d)

    @staticmethod
    def _buf_of(state: EngineState):
        """The async buffer 6-tuple, passed through unchanged by sync."""
        return (state.buf_vecs, state.buf_slots, state.buf_ready,
                state.buf_weight, state.buf_valid, state.buf_seq)

    def _wire_uplink(self, state: EngineState, vecs, slots,
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
        if self._wire_is_identity():
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

    def _update_refs(self, state: EngineState, part: Participation,
                     arrive, applied, rx_server, r: int):
        """Advance the per-client broadcast references: every receiving
        participant now holds the roundtripped rows it was just sent —
        its applied slots under ``downloads="assigned"``, the whole
        server matrix under ``"all_slots"`` (mirroring exactly what
        :meth:`_wire_downlink` billed).  Non-participants, drops, and
        stragglers keep their old references — that is the point."""
        if not self.cfg.codec.sparse:
            return state.ref_vecs, state.ref_round
        # work on the K sampled rows only (idx is without-replacement,
        # so the device scatter below touches each row once); the
        # untouched population rows never cross the host boundary
        idx = jnp.asarray(part.idx)
        sub = np.array(state.ref_vecs[idx])          # K rows, writable
        sub_rounds = np.array(state.ref_round[idx])
        self._advance_ref_rows(sub, sub_rounds, arrive, applied,
                               rx_server, r, self.strategy.downloads)
        return (state.ref_vecs.at[idx].set(jnp.asarray(sub)),
                state.ref_round.at[idx].set(jnp.asarray(sub_rounds)))

    @staticmethod
    def _advance_ref_rows(sub, sub_rounds, arrive, applied, rx_server, r,
                          downloads):
        """Advance K sampled reference rows in place — the one update
        both the resident scatter (:meth:`_update_refs`) and the mmap
        spill share, so their reference streams cannot diverge."""
        np_applied = np.asarray(applied)
        rx = np.asarray(rx_server, np.float32)
        for c in range(sub.shape[0]):
            if not arrive[c]:
                continue
            if downloads == "all_slots":
                sub[c] = rx
                sub_rounds[c] = r
            else:
                got = False
                for j in range(np_applied.shape[1]):
                    s = int(np_applied[c, j])
                    if s >= 0:
                        sub[c, s] = rx[s]
                        got = True
                if got:
                    sub_rounds[c] = r
        return sub, sub_rounds

    def _roundtrip_rows(self, server):
        """Encode→decode every server row through the *dense* wire codec
        (delta coding is upload-only) — what any receiver of a broadcast
        actually holds.  Returns ``(rx_rows, frame_lengths)``; float32
        is a bit-exact identity, so it skips the host round-trip and
        meters arithmetically (frame = 4·d bytes, codec-pinned)."""
        dense = CodecConfig(self.cfg.codec.name, sparse=False)
        if dense.name == "float32":
            return server, [4 * int(server.shape[1])] * int(server.shape[0])
        np_server = np.asarray(server, np.float32)
        rx = np.zeros_like(np_server)
        frame_len = []
        for s in range(np_server.shape[0]):
            frame = encode(np_server[s], dense)
            frame_len.append(len(frame))
            rx[s] = decode(frame, np_server.shape[1], dense)
        return jnp.asarray(rx), frame_len

    def _wire_tx_server(self, server):
        """The server matrix as the *clients* hold it: every row
        roundtripped through the dense codec, because the rows a client
        trains from arrived over last round's (possibly lossy)
        broadcast.  Metering is unaffected — download bytes are billed
        by :meth:`_wire_downlink` when the rows are pushed; this only
        stops ``client_step`` reading precision the wire never carried
        (see docs/async-runtime.md, byte metering).

        ``state.server`` entering round r+1 is the very array
        :meth:`_wire_downlink` roundtripped at the end of round r, so
        the downlink's result is cached by identity and the host
        encode/decode loop runs once per server matrix, not twice."""
        if self._wire_is_identity():
            return server
        cached = self._tx_cache
        if cached is not None and cached[0] is server:
            return cached[1]
        rx, _ = self._roundtrip_rows(server)
        self._tx_cache = (server, rx)
        return rx

    def _wire_downlink(self, server, counts, arrive, applied):
        """Run the broadcast through the wire too: every slot row is
        encoded (dense — delta coding is upload-only), metered, and
        decoded, and it is the *decoded* rows clients apply — a lossy
        codec degrades the downlink exactly as it would in deployment.
        ``down_bc`` is one frame per populated slot; ``down_pc`` is the
        per-client accounting over the frames receiving participants
        actually apply (legacy §6.7 accounting)."""
        np_counts = np.asarray(counts)
        rx_arr, frame_len = self._roundtrip_rows(server)
        if not self._wire_is_identity():
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
        Bit-identical to :meth:`_aggregate_async_host`, pinned by the
        conformance suite."""
        k, j = slots.shape
        active = jnp.asarray(part.active)
        stale = jnp.asarray(part.staleness, jnp.int32)
        flat = lambda a: jnp.broadcast_to(a[:, None], (k, j)).reshape(-1)
        up = (dec.reshape(k * j, -1).astype(jnp.float32),
              slots.reshape(-1).astype(jnp.int32),
              state.round_idx + flat(stale),
              self._discount[flat(stale)],
              flat(active) & (slots.reshape(-1) >= 0))
        server, counts, n_agg, n_buf, n_evict, buf = \
            self.executor.async_update(
                self.strategy, self._buf_of(state), up, state.round_idx,
                state.server.slots, self.cfg.async_min_uploads)
        return (server, counts, int(n_agg), int(n_buf), int(n_evict), buf)

    def _aggregate_async_host(self, state, dec, slots, part: Participation,
                              r):
        """Host-buffered aggregation (``async_buffer="host"``, and the
        path every async strategy with server-side hooks takes): the
        original numpy insert loop, kept verbatim as the executable
        reference the device path is pinned against — insert this
        round's uploads, then fold in every matured entry once
        ``async_min_uploads`` are available.

        Strategies with an ``assign`` hook have it re-run here over the
        matured buffer contents *at aggregation time* (buffer rows as
        single-upload clients, contribution mask as arrival), so
        FLIS-style dynamic membership is recomputed from what is
        actually being folded in — not from stale send-time tags.  The
        fold then goes through the strategy's ``server_update`` (the
        Alg. 2 default reproduces the legacy in-place write bit for
        bit).  Returns a full :class:`ServerState`."""
        cfg = self.cfg
        vecs = np.asarray(state.buf_vecs).copy()
        bslots = np.asarray(state.buf_slots).copy()
        ready = np.asarray(state.buf_ready).copy()
        weight = np.asarray(state.buf_weight).copy()
        valid = np.asarray(state.buf_valid).copy()
        seq = np.asarray(state.buf_seq).copy()

        np_dec = np.asarray(dec)
        np_slots = np.asarray(slots)
        active = np.asarray(part.active)
        stale = np.asarray(part.staleness)
        evicted = 0
        next_seq = int(seq[valid].max()) + 1 if valid.any() else 0
        for c in range(np_dec.shape[0]):
            if not active[c]:
                continue
            for j in range(np_dec.shape[1]):
                if np_slots[c, j] < 0:
                    continue
                free = np.nonzero(~valid)[0]
                if free.size:
                    i = free[0]
                else:       # overflow: evict the oldest *insertion*
                    occupied = np.where(valid, seq, np.iinfo(np.int32).max)
                    i = int(np.argmin(occupied))
                    evicted += 1
                vecs[i] = np_dec[c, j]
                bslots[i] = np_slots[c, j]
                ready[i] = r + int(stale[c])
                weight[i] = cfg.staleness_discount ** int(stale[c])
                valid[i] = True
                seq[i] = next_seq
                next_seq += 1

        server, counts, n_agg, n_buf, buf = self._fold_host_buffer(
            state, vecs, bslots, ready, weight, valid, seq, r)
        return server, counts, n_agg, n_buf, evicted, buf

    def _fold_host_buffer(self, state, vecs, bslots, ready, weight, valid,
                          seq, r):
        """Fold the matured host-buffer entries into the server (the
        tail of :meth:`_aggregate_async_host`, shared with the real
        transport's arrival-driven insert path — same maturity gate,
        same assign-at-aggregation hook, same ``server_update`` fold).
        Returns ``(server, counts, n_agg, n_buf, buf)``."""
        cfg = self.cfg
        # an entry whose staleness discount rounds to zero weight can never
        # contribute to the weighted mean — treat it as consumed noise so
        # its slot isn't wrongly marked populated (and then broadcast)
        mature = valid & (ready <= r)
        contrib = mature & (weight > 0.0)
        n_mature = int(mature.sum())
        if n_mature >= cfg.async_min_uploads:
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
        buf = (jnp.asarray(vecs), jnp.asarray(bslots), jnp.asarray(ready),
               jnp.asarray(weight), jnp.asarray(valid), jnp.asarray(seq))
        return server, counts, n_agg, int(valid.sum()), buf

    def _scatter_eval(self, state: EngineState, idx, merged, applied,
                      server, buf, refs, ef, acc_sub):
        """Scatter the merged sub-pytree back into the population,
        evaluate everyone, build the next state.  ``acc_sub`` is the
        fused program's per-client accuracy (full population when the
        identity gather was in effect), saving the separate eval pass."""
        if self._identity:
            cs = merged
            assignment = applied
        else:
            cs = jax.tree.map(lambda a, s: a.at[idx].set(s),
                              state.client_state, merged)
            assignment = jnp.full((self.n, self.strategy.j_slots), -1,
                                  jnp.int32).at[idx].set(applied)

        if acc_sub is not None and self._identity:
            acc = acc_sub
        else:
            acc = self.executor.evaluate(
                self.strategy, cs, self.data.x_test, self.data.y_test)
        # commit to a single device before any reduction: a mean over a
        # mesh-sharded accuracy vector reduces in device order, which is
        # ULP-different from the in-process sequential reduction (the
        # conformance suite pins the report bit-for-bit across backends)
        acc = jnp.asarray(np.asarray(acc))
        new_state = EngineState(
            round_idx=state.round_idx + 1, client_state=cs, server=server,
            buf_vecs=buf[0], buf_slots=buf[1], buf_ready=buf[2],
            buf_weight=buf[3], buf_valid=buf[4], buf_seq=buf[5],
            ref_vecs=refs[0], ref_round=refs[1], ef_residual=ef)
        return new_state, acc, assignment

    def _store_eval(self, state: EngineState, idx, merged, applied,
                    server, buf, refs, ef, sub_data):
        """mmap counterpart of :meth:`_scatter_eval`: the population
        already lives in the store (the round spilled the merged rows
        before this), so the next state keeps its zero-row placeholders.

        ``store_eval="full"`` re-gathers the whole population in
        ``store_eval_chunk`` blocks and evaluates each — per-client
        evaluation is an independent vmap lane on both executors, so
        the chunked accuracy vector is bit-identical to the resident
        monolithic eval.  ``"sampled"`` (the simulated-scale setting)
        evaluates only the K merged rows: the report's accuracy /
        assignment then cover the cohort, not the population."""
        if self.cfg.store_eval == "sampled":
            acc = self.executor.evaluate(
                self.strategy, merged, sub_data.x_test, sub_data.y_test)
            assignment = applied
        else:
            def gather_cs(ids):
                return jax.tree.map(jnp.asarray,
                                    self.store.gather(ids)["cs"])

            def gather_xy(ids):
                if self._streaming:
                    d = self.data.gather_clients(ids)
                    return d.x_test, d.y_test
                jids = jnp.asarray(ids)
                return self.data.x_test[jids], self.data.y_test[jids]

            acc = executors.evaluate_population(
                self.executor, self.strategy, gather_cs, gather_xy,
                self.n, self.cfg.store_eval_chunk)
            assignment = jnp.full((self.n, self.strategy.j_slots), -1,
                                  jnp.int32).at[jnp.asarray(idx)].set(
                applied)
        acc = jnp.asarray(np.asarray(acc))
        new_state = EngineState(
            round_idx=state.round_idx + 1,
            client_state=state.client_state, server=server,
            buf_vecs=buf[0], buf_slots=buf[1], buf_ready=buf[2],
            buf_weight=buf[3], buf_valid=buf[4], buf_seq=buf[5],
            ref_vecs=refs[0], ref_round=refs[1], ef_residual=ef)
        return new_state, acc, assignment
