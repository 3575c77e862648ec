"""TransportEngine: the server side of the real-transport runtime.

Runs the same staged federated round as
:class:`repro.fl.runtime.engine.Engine` — schedule → broadcast →
client step → uplink codec → (assign) → aggregate → server_update →
downlink → eval — but with every client-side stage executed by worker
peers behind a wire: the broadcast rows go out as encoded frames inside
WORK messages, the uplink comes back as the workers' actual codec
frames inside UPLOAD messages, and the block evaluations return as EVAL
messages.  The server half — assignment, aggregation, server state,
the metered downlink, sparse-ref tracking for decode — is the engine's
own :meth:`~repro.fl.runtime.engine.Engine.serve_uploads`, called with
the uploads decoded off the wire, so the two rounds cannot drift.

Conformance contract: with ``transport="loopback"`` and the identity
wire (dense float32), a run is **bit-identical** to the in-process
engine — same reports (every pre-transport field), same codec-metered
byte totals, same final state — pinned by ``tests/test_transport.py``.
The wire gauges (``wire_tx_bytes`` / ``wire_rx_bytes``) are additional:
they count framed bytes that actually crossed the transport, which the
in-process engine by definition has none of.

Async mode is *arrival-driven*: workers hold straggling uploads and
flush them in later rounds tagged with their source round; the server
buffers whatever actually arrives, weighted by the **observed** lag
(``discount ** (arrival − source)``), and records the observed
staleness summary in each round's report/event.  Arrival order (worker
rank-major) replaces the engine's cohort insertion order, so async
transport runs are semantically equivalent but not bit-pinned.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.fl.runtime.codec import decode
from repro.fl.runtime.engine import Engine, EngineState, RoundReport
from repro.fl.runtime.scheduler import arrival_participation
from repro.fl.transport import framing
from repro.fl.transport.faults import FaultPlan, RetryPolicy
from repro.fl.transport.loopback import LoopbackTransport
from repro.fl.transport.messages import (DownClient, Downlink, Eval,
                                         MsgKind, Upload, Work, WorkClient)
from repro.fl.transport.socket_transport import SocketTransport
from repro.fl.transport.worker import ClientWorker, block_range


class TransportEngine:
    """Round orchestrator over a real transport (loopback or socket)."""

    def __init__(self, strategy, data, cfg, telemetry=None,
                 faults: FaultPlan | None = None,
                 retry: RetryPolicy | None = None,
                 spec: dict | None = None):
        if cfg.transport not in ("loopback", "socket"):
            raise ValueError(
                f"TransportEngine runs transport='loopback' | 'socket'; "
                f"transport={cfg.transport!r} is the in-process Engine")
        if cfg.transport == "socket" and spec is None:
            raise ValueError(
                "transport='socket' needs a worker spec dict (scenario "
                "kwargs for repro.launch.fed_train.build_scenario) so "
                "worker subprocesses can rebuild the identical scenario")
        self.eng = Engine(strategy, data, cfg, telemetry=telemetry)
        self.cfg = cfg
        self.obs = self.eng.obs
        self.faults = faults or FaultPlan()
        self.retry = retry or RetryPolicy()
        self.spec = spec

    # -- lifecycle -----------------------------------------------------------

    def run(self, key: jax.Array, rounds: int | None = None
            ) -> tuple[EngineState | None, list[RoundReport]]:
        """Run the configured rounds over the transport.

        Returns ``(final_state, reports)``.  Loopback assembles the
        final :class:`EngineState` from the server lanes plus the
        workers' block state (the conformance pin needs it); a socket
        run returns ``state=None`` — the population lives in worker
        processes that have already exited.
        """
        eng = self.eng
        k_init, k_rounds = jax.random.split(key)
        state = eng.init(k_init)
        transport, workers = self._open(state, key)
        try:
            reports: list[RoundReport] = []
            n_rounds = self.cfg.rounds if rounds is None else rounds
            for r in range(n_rounds):
                if self.obs.enabled:
                    self.obs.begin_round(r)
                with self.obs.span("round"):
                    state, rep = self._round(
                        transport, state, jax.random.fold_in(k_rounds, r),
                        r)
                    self.obs.fence(state)
                self.obs.on_round(rep)
                reports.append(rep)
            self._shutdown(transport)
            if workers is not None:
                state = self._assemble_state(state, workers)
            else:
                state = None
        finally:
            transport.close()
        return state, reports

    def _open(self, state: EngineState, key: jax.Array):
        cfg, eng = self.cfg, self.eng
        if cfg.transport == "socket":
            spec = dict(self.spec)
            spec["runtime"] = self._runtime_dict()
            spec["key"] = [int(w) for w in np.asarray(key, np.uint32)]
            if self.faults.delay or self.faults.drop:
                spec["faults"] = {"delay": list(self.faults.delay),
                                  "drop": list(self.faults.drop)}
            return SocketTransport.launch(spec, cfg.workers,
                                          connect_timeout=
                                          self.retry.timeout * 10), None
        workers = []
        for rank in range(cfg.workers):
            lo, hi = block_range(eng.n, cfg.workers, rank)
            sl = slice(lo, hi)
            workers.append(ClientWorker(
                rank, lo, hi, eng.strategy, cfg,
                block_cs=jax.tree.map(lambda a: a[sl], state.client_state),
                block_data=jax.tree.map(lambda a: a[sl], eng.data),
                ref_vecs=(state.ref_vecs[sl] if cfg.codec.sparse else None),
                ref_round=(state.ref_round[sl] if cfg.codec.sparse
                           else None),
                ef=(state.ef_residual[sl] if cfg.codec.error_feedback
                    else None),
                faults=self.faults))
        return LoopbackTransport(workers, faults=self.faults), workers

    def _runtime_dict(self) -> dict:
        from repro.fl.transport.worker import runtime_config_to_dict
        return runtime_config_to_dict(self.cfg)

    def _shutdown(self, transport) -> None:
        for rank in transport.ranks:
            transport.send(rank, MsgKind.SHUTDOWN, b"")
        for rank in transport.ranks:
            kind, _, _ = self._recv(transport, rank, MsgKind.BYE)

    def _assemble_state(self, state: EngineState, workers) -> EngineState:
        """Loopback final state: server lanes from the server, client
        rows (and error-feedback residuals — client-side wire state)
        re-assembled from the worker blocks in rank order."""
        cs = jax.tree.map(lambda *blocks: jnp.concatenate(blocks, axis=0),
                          *[w.block_cs for w in workers])
        ef = state.ef_residual
        if self.cfg.codec.error_feedback:
            ef = jnp.concatenate(
                [jnp.asarray(w.ef) for w in workers], axis=0)
        return state._replace(client_state=cs, ef_residual=ef)

    # -- wire helpers --------------------------------------------------------

    def _recv(self, transport, rank: int, want: int):
        """One expected message, under the retry policy: disconnects and
        timeouts back off exponentially and retry; attempts exhausted →
        the last error propagates."""
        last = None
        for attempt in range(self.retry.attempts):
            if attempt:
                time.sleep(self.retry.backoff * 2 ** (attempt - 1))
                transport.reconnect(rank)
            try:
                kind, payload, nbytes = transport.recv(
                    rank, timeout=self.retry.timeout)
            except (framing.DisconnectError, TimeoutError) as e:
                last = e
                continue
            if kind != want:
                raise framing.WireError(
                    f"expected message kind {want} from worker {rank}, "
                    f"got {kind}")
            return kind, payload, nbytes
        raise last

    # -- one round -----------------------------------------------------------

    def _round(self, transport, state: EngineState, round_key, r: int
               ) -> tuple[EngineState, RoundReport]:
        eng, cfg, obs = self.eng, self.cfg, self.obs
        strategy = eng.strategy
        sync = cfg.aggregation == "sync"
        wire_tx = wire_rx = 0

        with obs.span("schedule"):
            part = eng.scheduler.sample(r, round_key)
            np_idx = np.asarray(part.idx)
            active = np.asarray(part.active)
            sched_stale = np.asarray(part.staleness)

        # the engine's exact per-client key stream: split over the full
        # population, then slice the cohort
        keys = np.asarray(jax.random.split(round_key, eng.n))[np_idx]

        with obs.span("broadcast_encode"):
            rows = eng.row_frames(state.server.slots)
            d = strategy.vec_dim

        # cohort → worker blocks (position k in cohort order per rank)
        n_workers = len(transport.ranks)
        rank_of = np.empty((eng.n,), np.int32)
        for rank in transport.ranks:
            lo, hi = block_range(eng.n, n_workers, rank)
            rank_of[lo:hi] = rank
        by_rank: dict[int, list[int]] = {rank: [] for rank in
                                         transport.ranks}
        for k, g in enumerate(np_idx):
            by_rank[int(rank_of[g])].append(k)

        with obs.span("wire_tx"):
            for rank in transport.ranks:
                clients = tuple(
                    WorkClient(gidx=int(np_idx[k]),
                               key=(int(keys[k, 0]), int(keys[k, 1])),
                               active=bool(active[k]),
                               staleness=int(sched_stale[k]))
                    for k in by_rank[rank])
                wire_tx += transport.send(
                    rank, MsgKind.WORK,
                    Work(round_idx=r, dim=d, rows=tuple(rows),
                         clients=clients).pack())

        # collect the round's real uplink frames
        K, j = eng.scheduler.k, strategy.j_slots
        dec = np.zeros((K, j, d), np.float32)
        slots = np.full((K, j), -1, np.int32)
        received = np.zeros((K,), bool)
        recv_stale = np.zeros((K,), np.int32)
        arrivals: list[tuple[int, int, np.ndarray, int]] = []
        pos_of = {int(g): k for k, g in enumerate(np_idx)}
        sparse = cfg.codec.sparse
        refs_np = np.asarray(state.ref_vecs) if sparse else None
        up_bytes = 0
        with obs.span("wire_rx"):
            uploads = []
            for rank in transport.ranks:
                _, payload, nbytes = self._recv(transport, rank,
                                                MsgKind.UPLOAD)
                wire_rx += nbytes
                uploads.append(Upload.unpack(payload))
        with obs.span("uplink_codec"):
            for up in uploads:
                for e in up.entries:
                    for j_idx, s, frame in e.frames:
                        up_bytes += 4 + len(frame)
                        ref = refs_np[e.gidx, s] if sparse else None
                        vec = decode(frame, d, cfg.codec, ref=ref)
                        if sync:
                            k = pos_of[e.gidx]
                            dec[k, j_idx] = vec
                            slots[k, j_idx] = s
                        else:
                            arrivals.append(
                                (e.gidx, s, vec, r - e.src_round))
                            if e.src_round == r:
                                # on-time sender in this round's cohort:
                                # the server knows its proposed tags, so
                                # applied_slots can route rows back to it
                                slots[pos_of[e.gidx], j_idx] = s
                    if sync:
                        k = pos_of[e.gidx]
                        received[k] = True
                        recv_stale[k] = e.staleness

        observed = entries = None
        if sync:
            # the sync barrier: an upload counts only if it arrived in
            # its own round (without faults this equals the scheduled
            # active & staleness==0 mask, which is the conformance pin)
            arrive = received & (recv_stale == 0)
        else:
            # whatever arrived is mature now, weighted by its observed
            # lag; every active client trained and applies the
            # broadcast, matching the engine's async receivers
            arrive = active
            entries = [(vec, s, r, cfg.staleness_discount ** int(lag))
                       for _, s, vec, lag in arrivals]
            observed = arrival_participation(
                [g for g, _, _, _ in arrivals],
                [lag for _, _, _, lag in arrivals]).summary()
        served = eng.serve_uploads(state, part, jnp.asarray(dec),
                                   jnp.asarray(slots), arrive, r,
                                   entries=entries)

        np_applied = np.asarray(served.applied)
        with obs.span("wire_tx"):
            down_rows = eng.row_frames(served.server.slots)
            for rank in transport.ranks:
                clients = tuple(
                    DownClient(gidx=int(np_idx[k]),
                               arrive=bool(arrive[k]),
                               applied=tuple(int(s)
                                             for s in np_applied[k]))
                    for k in by_rank[rank])
                wire_tx += transport.send(
                    rank, MsgKind.DOWNLINK,
                    Downlink(round_idx=r, dim=d, rows=tuple(down_rows),
                             clients=clients).pack())

        with obs.span("eval"):
            accs = []
            with obs.span("wire_rx"):
                for rank in transport.ranks:
                    _, payload, nbytes = self._recv(transport, rank,
                                                    MsgKind.EVAL)
                    wire_rx += nbytes
                    accs.append(np.asarray(Eval.unpack(payload).acc))
            acc = jnp.asarray(np.concatenate(accs))
            obs.fence(acc)

        # the client rows and error-feedback residuals are worker-owned;
        # see run()
        new_state = eng.next_state(state, served, state.client_state,
                                   state.ef_residual)
        return new_state, eng.report(
            r, part, served, up_bytes, acc,
            eng.rows.assignment(part.idx, served.applied),
            wire_tx_bytes=wire_tx, wire_rx_bytes=wire_rx,
            observed_staleness=observed)
