"""Client-worker runtime: one process (or in-process loopback peer)
owning a contiguous block of the client population.

The worker is a message-driven state machine — ``handle(kind, payload)
→ [(kind, payload), ...]`` — with no transport knowledge of its own:
the socket main loop (:func:`run_socket_worker`) and the in-memory
loopback both push the same framed bytes through it, which is what
makes the loopback run a faithful reference for the multi-process one.

Per round the worker:

1. ``WORK``  — decodes the broadcast server rows off the dense wire
   codec, trains its block's sampled clients
   (:class:`~repro.fl.runtime.executors.InProcessExecutor` — per-client
   vmap lanes are independent, so a block vmap equals the engine's
   full-population vmap lane for lane), encodes each surviving upload
   into the *actual* codec frames (sparse refs and error-feedback
   residuals are worker-owned state: the client side of the wire), and
   answers ``UPLOAD``.  Under async aggregation a straggling client's
   frames are held back and flushed with a later round's UPLOAD, tagged
   with their source round — observed staleness on the server is real
   arrival lag, not an injected schedule.
2. ``DOWNLINK`` — decodes the post-aggregate rows, applies them per the
   server's arrive/applied routing, advances its broadcast references,
   evaluates its whole block, and answers ``EVAL``.

Run as a subprocess for ``transport="socket"``:

    python -m repro.fl.transport.worker --spec spec.json --rank R \
        --host 127.0.0.1 --port P

The spec (written by the socket transport) rebuilds the *identical*
scenario via ``repro.launch.fed_train.build_scenario`` and the identical
initial population via ``Engine.init`` on the shared init key — worker
block state is a slice of exactly the state the server holds.
"""
from __future__ import annotations

import argparse
import json
import socket

import jax
import jax.numpy as jnp
import numpy as np

from repro.fl.runtime.codec import CodecConfig, decode, ef_encode, encode
from repro.fl.runtime.engine import Engine, RuntimeConfig
from repro.fl.runtime.rows import advance_ref_rows
from repro.fl.runtime.executors import InProcessExecutor
from repro.fl.runtime.scheduler import SchedulerConfig
from repro.fl.transport import framing
from repro.fl.transport.faults import FaultPlan
from repro.fl.transport.messages import (Downlink, Eval, Hello, MsgKind,
                                         Upload, UploadEntry, Work)


def block_range(n: int, workers: int, rank: int) -> tuple[int, int]:
    """Contiguous client block [lo, hi) owned by ``rank`` of ``workers``."""
    return rank * n // workers, (rank + 1) * n // workers


def runtime_config_to_dict(cfg: RuntimeConfig) -> dict:
    import dataclasses
    return dataclasses.asdict(cfg)


def runtime_config_from_dict(d: dict) -> RuntimeConfig:
    d = dict(d)
    d["scheduler"] = SchedulerConfig(**d["scheduler"])
    d["codec"] = CodecConfig(**d["codec"])
    return RuntimeConfig(**d)


class ClientWorker:
    """The message-driven client-side half of the round protocol."""

    def __init__(self, rank: int, lo: int, hi: int, strategy,
                 cfg: RuntimeConfig, block_cs, block_data,
                 ref_vecs=None, ref_round=None, ef=None,
                 faults: FaultPlan | None = None):
        self.rank, self.lo, self.hi = rank, lo, hi
        self.strategy = strategy
        self.cfg = cfg
        self.executor = InProcessExecutor()
        self.block_cs = block_cs
        self.block_data = block_data
        # client-side wire state, numpy for in-place per-frame updates
        self.ref_vecs = (None if ref_vecs is None
                         else np.array(np.asarray(ref_vecs, np.float32)))
        self.ref_round = (None if ref_round is None
                          else np.array(np.asarray(ref_round, np.int32)))
        self.ef = None if ef is None \
            else np.array(np.asarray(ef, np.float32))
        self.faults = faults or FaultPlan()
        self._dense = CodecConfig(cfg.codec.name, sparse=False)
        self._sync = cfg.aggregation == "sync"
        # async: encoded uploads held until their flush round arrives
        self._held: list[tuple[int, UploadEntry]] = []  # (flush_round, e)
        self._ctx = None        # in-flight round: set by WORK, used by
        #                         DOWNLINK (train → apply is split by
        #                         the server's aggregation in between)

    # -- dispatch ------------------------------------------------------------

    def handle(self, kind: int, payload: bytes) -> list[tuple[int, bytes]]:
        if kind == MsgKind.WORK:
            return [(MsgKind.UPLOAD, self._work(Work.unpack(payload)))]
        if kind == MsgKind.DOWNLINK:
            return [(MsgKind.EVAL,
                     self._downlink(Downlink.unpack(payload)))]
        if kind == MsgKind.SHUTDOWN:
            return [(MsgKind.BYE, b"")]
        raise framing.WireError(
            f"worker {self.rank}: unexpected message kind {kind}")

    # -- round halves --------------------------------------------------------

    def _decode_rows(self, rows, dim) -> jnp.ndarray:
        out = np.zeros((len(rows), dim), np.float32)
        for s, frame in enumerate(rows):
            out[s] = decode(frame, dim, self._dense)
        return jnp.asarray(out)

    def _work(self, msg: Work) -> bytes:
        r = msg.round_idx
        tx_server = self._decode_rows(msg.rows, msg.dim)
        local = np.asarray([c.gidx - self.lo for c in msg.clients],
                           np.int32)
        keys = jnp.asarray(
            np.asarray([[c.key[0], c.key[1]] for c in msg.clients],
                       np.uint32))
        jloc = jnp.asarray(local)
        sub_cs = jax.tree.map(lambda a: a[jloc], self.block_cs)
        sub_data = jax.tree.map(lambda a: a[jloc], self.block_data)
        new_sub, vecs, slots = self.executor.train(
            self.strategy, sub_cs, tx_server, sub_data, keys)

        codec_cfg = self.cfg.codec
        np_vecs = np.asarray(vecs, np.float32)
        np_slots = np.asarray(slots)
        entries = []
        for c, wc in enumerate(msg.clients):
            if not wc.active or self.faults.dropped(r, wc.gidx):
                continue                 # upload lost — nothing on the wire
            b = int(local[c])
            frames = []
            for j in range(np_vecs.shape[1]):
                s = int(np_slots[c, j])
                if s < 0:
                    continue             # nothing shared in this slot
                ref = (self.ref_vecs[b, s]
                       if codec_cfg.sparse else None)
                if self.ef is not None:
                    frame, self.ef[b, s] = ef_encode(
                        np_vecs[c, j], codec_cfg, self.ef[b, s], ref=ref)
                else:
                    frame = encode(np_vecs[c, j], codec_cfg, ref=ref)
                frames.append((j, s, frame))
            delay = wc.staleness + self.faults.delay_for(r, wc.gidx)
            entry = UploadEntry(gidx=wc.gidx, src_round=r,
                                staleness=delay, frames=tuple(frames))
            if self._sync or delay == 0:
                # sync: late frames were still *sent* this round — the
                # server meters them and lets the barrier discard them
                entries.append(entry)
            else:
                self._held.append((r + delay, entry))
        if not self._sync:
            flushed = [e for fr, e in self._held if fr <= r]
            self._held = [(fr, e) for fr, e in self._held if fr > r]
            entries.extend(flushed)

        self._ctx = (r, jloc, sub_cs, new_sub, msg.clients)
        return Upload(round_idx=r, entries=tuple(entries)).pack()

    def _downlink(self, msg: Downlink) -> bytes:
        if self._ctx is None or self._ctx[0] != msg.round_idx:
            raise framing.WireError(
                f"worker {self.rank}: DOWNLINK for round {msg.round_idx} "
                f"without a matching WORK in flight")
        r, jloc, sub_cs, new_sub, work_clients = self._ctx
        self._ctx = None
        by_gidx = {c.gidx: c for c in msg.clients}
        ordered = [by_gidx[w.gidx] for w in work_clients]
        arrive = np.asarray([c.arrive for c in ordered], bool)
        applied = np.asarray([c.applied for c in ordered], np.int32)
        rx_server = self._decode_rows(msg.rows, msg.dim)
        merged = self.executor.apply_merge(
            self.strategy, new_sub, jnp.asarray(applied), rx_server,
            sub_cs, jnp.asarray(arrive))
        self.block_cs = jax.tree.map(
            lambda a, s: a.at[jloc].set(s), self.block_cs, merged)
        if self.cfg.codec.sparse:
            local = np.asarray(jloc)
            self.ref_vecs[local], self.ref_round[local] = advance_ref_rows(
                self.ref_vecs[local], self.ref_round[local], arrive,
                applied, np.asarray(rx_server), r, self.strategy.downloads)
        acc = self.executor.evaluate(
            self.strategy, self.block_cs,
            self.block_data.x_test, self.block_data.y_test)
        return Eval(round_idx=r, acc=np.asarray(acc, np.float32)).pack()


# -- socket main loop --------------------------------------------------------

def _recv_exact(conn: socket.socket):
    def inner(n: int) -> bytes:
        chunks = []
        remaining = n
        while remaining:
            chunk = conn.recv(remaining)
            if not chunk:
                break                    # EOF — framing decides how loud
            chunks.append(chunk)
            remaining -= len(chunk)
        return b"".join(chunks)
    return inner


def run_socket_worker(worker: ClientWorker, host: str, port: int):
    """Connect to the transport server and serve rounds until SHUTDOWN."""
    with socket.create_connection((host, port)) as conn:
        conn.sendall(framing.pack_frame(
            MsgKind.HELLO,
            Hello(worker.rank, worker.lo, worker.hi).pack()))
        recv = _recv_exact(conn)
        while True:
            kind, payload = framing.read_frame(recv)
            for out_kind, out_payload in worker.handle(kind, payload):
                conn.sendall(framing.pack_frame(out_kind, out_payload))
            if kind == MsgKind.SHUTDOWN:
                return


def worker_from_spec(spec: dict, rank: int) -> ClientWorker:
    """Rebuild the worker's slice of the federated scenario from the
    socket transport's spec: same scenario builder, same init key →
    the block state is bit-identical to the server's rows."""
    from repro.launch.fed_train import build_scenario
    cfg = runtime_config_from_dict(spec["runtime"])
    _, data, _, _, strategy = build_scenario(**spec["scenario"])
    engine = Engine(strategy, data, cfg)
    key = jnp.asarray(np.asarray(spec["key"], np.uint32))
    k_init, _ = jax.random.split(key)
    state = engine.init(k_init)
    lo, hi = block_range(engine.n, cfg.workers, rank)
    sl = slice(lo, hi)
    block_cs = jax.tree.map(lambda a: a[sl], state.client_state)
    block_data = jax.tree.map(lambda a: a[sl], data)
    ref_vecs = state.ref_vecs[sl] if cfg.codec.sparse else None
    ref_round = state.ref_round[sl] if cfg.codec.sparse else None
    ef = state.ef_residual[sl] if cfg.codec.error_feedback else None
    faults = FaultPlan(**spec.get("faults", {}))
    return ClientWorker(rank, lo, hi, engine.strategy, cfg, block_cs,
                        block_data, ref_vecs=ref_vecs,
                        ref_round=ref_round, ef=ef, faults=faults)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Federated transport client worker (one block of "
                    "the client population, spoken to over the "
                    "length-prefixed wire)")
    ap.add_argument("--spec", required=True,
                    help="JSON scenario/runtime spec written by the "
                         "socket transport")
    ap.add_argument("--rank", type=int, required=True)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    args = ap.parse_args(argv)
    with open(args.spec) as fh:
        spec = json.load(fh)
    worker = worker_from_spec(spec, args.rank)
    run_socket_worker(worker, args.host, args.port)


if __name__ == "__main__":
    main()
