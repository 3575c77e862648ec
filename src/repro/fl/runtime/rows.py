"""Where the population's client rows live: the engine's row seam.

The engine picks one rows object at construction — :class:`ResidentRows`
(device arrays inside ``EngineState``) or :class:`StoreRows` (the mmap
:class:`~repro.fl.store.client_store.ClientStore`) — and the round goes
through it without asking which: ``init``, ``resume`` / ``flush``
(checkpoints), ``io`` (host bytes read and written), ``gather`` (the
round's :class:`Cohort`), ``track_refs`` (the sparse wire's broadcast
references) and ``commit`` (the merged cohort back, evaluation and the
next state: ``(new_state, acc, assignment)``).
"""
from __future__ import annotations

import tempfile
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.fl.runtime import executors
from repro.fl.runtime.scheduler import Participation
from repro.fl.runtime.strategy import ensure_server_state
from repro.fl.store.client_store import ClientStore


class Cohort(NamedTuple):
    """The round's sampled client rows, as a rows object gathered them."""
    idx: Any                # (K,) their client ids
    cs: Any                 # client-state sub-pytree (K rows)
    data: Any               # their data (a ClientData or streamed gather)
    keys: jax.Array         # their per-client rng keys
    refs: Any = None        # mmap + sparse wire: (ref_vecs, ref_round) rows


def _no_lanes():
    """Zero-size stand-ins for the ref and error-feedback lanes."""
    return (jnp.zeros((0, 0, 0), jnp.float32), jnp.zeros((0,), jnp.int32),
            jnp.zeros((0, 0, 0), jnp.float32))


def advance_ref_rows(ref_vecs, ref_round, arrive, applied, rx_server, r,
                     downloads):
    """K sampled clients' reference rows, advanced, as new numpy arrays:
    every receiving participant now holds the roundtripped rows it was
    just sent — its applied slots under ``downloads="assigned"``, the
    whole server matrix under ``"all_slots"`` (exactly what the downlink
    billed).  Drops and stragglers keep their old references.  The
    resident scatter, the mmap spill and the transport worker all
    advance their rows here, so their reference streams cannot
    diverge."""
    sub = np.array(ref_vecs, np.float32)
    sub_rounds = np.array(ref_round)
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


class _Rows:
    """What both stores share: the engine they serve, and the
    population's assignment table."""

    identity = False    # the cohort is the whole population, in order
    store = None

    def __init__(self, eng):
        self.eng = eng

    def assignment(self, idx, applied):
        """The population's (n, j) assignment table: the cohort's applied
        slots, −1 for every client outside it."""
        if self.identity:
            return applied
        eng = self.eng
        return jnp.full((eng.n, eng.strategy.j_slots), -1,
                        jnp.int32).at[idx].set(applied)


class ResidentRows(_Rows):
    """The population's rows as device arrays inside ``EngineState``
    (``client_store="resident"``): an indexed gather of the cohort, a
    scatter back at commit, and the sparse wire's references as two
    population lanes of the state."""

    def __init__(self, eng):
        super().__init__(eng)
        # uniform full participation samples idx = arange(N): skip the
        # identity gather/scatter so the legacy-default path copies
        # nothing (the dominant configuration for every benchmark)
        self.identity = (eng.scheduler.k == eng.n
                         and eng.cfg.scheduler.sampling == "uniform")

    def init(self, key: jax.Array):
        eng = self.eng
        cs, server = eng.strategy_init(key)
        server = ensure_server_state(server)
        n, n_slots, d = eng.n, eng.strategy.n_slots, eng.strategy.vec_dim
        ref_vecs, ref_round, ef = _no_lanes()
        if eng.cfg.codec.sparse:
            ref_vecs = jnp.zeros((n, n_slots, d), jnp.float32)
            ref_round = jnp.full((n,), -1, jnp.int32)
        if eng.cfg.codec.error_feedback:
            ef = jnp.zeros((n, n_slots, d), jnp.float32)
        if eng.cfg.backend == "shardmap":
            # placed where the round programs return them, so round 0
            # compiles nothing that round 1 does not reuse
            cs = eng.executor.place(cs)
            server = eng.executor.place(server, replicated=True)
        return cs, server, (ref_vecs, ref_round), ef

    def resume(self, key: jax.Array) -> None:
        """Nothing to reopen: the restored state holds every row."""

    def flush(self) -> None:
        """Nothing to flush: a checkpoint of the state holds every row
        (no store manifest rides along)."""

    def io(self) -> tuple[int, int]:
        return 0, 0

    def gather(self, state, part: Participation, keys) -> Cohort:
        if self.identity:
            return Cohort(part.idx, state.client_state, self.eng.data, keys)
        keys = keys[part.idx]
        cs = jax.tree.map(lambda a: a[part.idx], state.client_state)
        return Cohort(part.idx, cs,
                      jax.tree.map(lambda a: a[part.idx], self.eng.data), keys)

    def track_refs(self, state, idx, cohort_refs, arrive, applied,
                   rx_server, r: int):
        """Advance the sampled clients' rows of the two reference lanes
        (:func:`advance_ref_rows`); untouched population rows never
        cross the host boundary."""
        if not self.eng.cfg.codec.sparse:
            return state.ref_vecs, state.ref_round
        # idx is without-replacement, so the scatter touches each row once
        idx = jnp.asarray(idx)
        sub, sub_rounds = advance_ref_rows(
            state.ref_vecs[idx], state.ref_round[idx], arrive, applied,
            rx_server, r, self.eng.strategy.downloads)
        return (state.ref_vecs.at[idx].set(jnp.asarray(sub)),
                state.ref_round.at[idx].set(jnp.asarray(sub_rounds)))

    def commit(self, state, cohort: Cohort, served, ef):
        """Scatter the merged cohort into the population, evaluate
        everyone (unless the fused program did), build the next state
        around the served server half."""
        eng, obs = self.eng, self.eng.obs
        with obs.span("eval"):
            cs = served.merged if self.identity else jax.tree.map(
                lambda a, s: a.at[cohort.idx].set(s), state.client_state,
                served.merged)
            assignment = self.assignment(cohort.idx, served.applied)
            acc = served.acc
            if acc is None:
                acc = eng.executor.evaluate(eng.strategy, cs,
                                            eng.data.x_test, eng.data.y_test)
            # commit to a single device before any reduction: a mean over
            # a mesh-sharded accuracy vector reduces in device order, which
            # is ULP-different from the in-process sequential reduction
            # (the conformance suite pins the report across backends)
            acc = jnp.asarray(np.asarray(acc))
            new_state = eng.next_state(state, served, cs, ef)
            obs.fence(acc)
        return new_state, acc, assignment


class StoreRows(_Rows):
    """The population's rows in a memory-mapped
    :class:`~repro.fl.store.client_store.ClientStore`
    (``client_store="mmap"``): only the K sampled rows are ever device
    arrays.  The store holds the client state and, on the sparse wire,
    each client's references; the state carries zero-row placeholders
    for both.  See ``docs/client-store.md``."""

    def __init__(self, eng):
        super().__init__(eng)
        self.store: ClientStore | None = None
        self.streaming = hasattr(eng.data, "gather_clients")

    def init(self, key: jax.Array):
        """Open the client store; the state keeps zero-row placeholders
        for the client rows and the reference lanes (they live in it).

        Strategies exposing the O(K) init hooks (``init_cohort(key,
        ids, n) == init(key, n)[0][ids]`` bit-for-bit, plus
        ``init_server``) never materialize the population at all —
        unwritten store rows are regenerated per sampled cohort.
        Hookless strategies fall back to one full ``init`` whose rows
        are served by index: O(N) host RAM once, still O(K) device per
        round."""
        eng = self.eng
        strat, n = eng.strategy, eng.n
        cohort = getattr(strat, "init_cohort", None)
        init_server = getattr(strat, "init_server", None)
        if cohort is not None and init_server is not None:
            server = ensure_server_state(init_server(key, n))
            row = jax.tree.map(lambda a: np.asarray(a)[0],
                               cohort(key, np.asarray([0]), n))

            def cs_init(ids):
                return jax.tree.map(np.asarray, cohort(key, ids, n))
        else:
            cs, server = eng.strategy_init(key)
            server = ensure_server_state(server)
            rows = jax.tree.map(np.asarray, cs)
            row = jax.tree.map(lambda a: a[0], rows)

            def cs_init(ids):
                return jax.tree.map(lambda a: a[ids], rows)

        sparse, d = eng.cfg.codec.sparse, strat.vec_dim
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

        root = eng.cfg.store_dir or tempfile.mkdtemp(prefix="client_store_")
        self.store = ClientStore(root, n, template, init_fn=init_fn)
        placeholder = jax.tree.map(
            lambda a: jnp.zeros((0,) + np.asarray(a).shape,
                                np.asarray(a).dtype), row)
        ref_vecs, ref_round, ef = _no_lanes()
        return placeholder, server, (ref_vecs, ref_round), ef

    def resume(self, key: jax.Array) -> None:
        """Reopen the store keyed by THIS run's ``k_init``, so rows never
        sampled before the checkpoint fault in exactly as the
        uninterrupted run would have generated them (the ``like`` state
        a caller built for ``checkpointing.restore`` may have used a
        different key)."""
        self.init(key)

    def flush(self) -> dict:
        """Flush the rows for a checkpoint and return the store manifest
        that rides along: the checkpoint is only the replicated state,
        so checkpoint + store dir resume together (valid at the latest
        checkpoint: store rows advance past older ones)."""
        self.store.flush()
        return self.store.manifest

    def io(self) -> tuple[int, int]:
        return self.store.io_read_bytes, self.store.io_written_bytes

    def gather(self, state, part: Participation, keys) -> Cohort:
        """The K sampled rows off the host store (digest-verified;
        never-spilled rows regenerated by the strategy's deterministic
        init), with the same per-client keys as the resident gather, so
        the round is bit-identical."""
        eng, np_ids = self.eng, np.asarray(part.idx)
        keys = keys[part.idx]
        bundle = self.store.gather(np_ids)
        cs = jax.tree.map(jnp.asarray, bundle["cs"])
        refs = None
        if eng.cfg.codec.sparse:
            refs = (jnp.asarray(bundle["ref_vecs"]),
                    jnp.asarray(bundle["ref_round"]))
        data = (eng.data.gather_clients(np_ids) if self.streaming else
                jax.tree.map(lambda a: a[part.idx], eng.data))
        return Cohort(part.idx, cs, data, keys, refs)

    def track_refs(self, state, idx, cohort_refs, arrive, applied,
                   rx_server, r: int):
        """Advance the cohort's reference rows (spilled at commit)."""
        if cohort_refs is None:
            return None
        sub, sub_rounds = advance_ref_rows(
            *cohort_refs, arrive, applied, rx_server, r,
            self.eng.strategy.downloads)
        return jnp.asarray(sub), jnp.asarray(sub_rounds)

    def commit(self, state, cohort: Cohort, served, ef):
        """Spill the merged cohort (and its advanced references) back to
        the store — after this the round holds no per-client device
        state beyond the K rows — then evaluate.

        ``store_eval="full"`` re-gathers the whole population in
        ``store_eval_chunk`` blocks and evaluates each — per-client
        evaluation is an independent vmap lane on both executors, so
        the chunked accuracy vector is bit-identical to the resident
        monolithic eval.  ``"sampled"`` (the simulated-scale setting)
        evaluates only the K merged rows: the report's accuracy /
        assignment then cover the cohort, not the population."""
        eng, obs = self.eng, self.eng.obs
        np_ids = np.asarray(cohort.idx)
        with obs.span("spill"):
            bundle = {"cs": jax.tree.map(np.asarray, served.merged)}
            if served.refs is not None:
                bundle["ref_vecs"] = np.asarray(served.refs[0], np.float32)
                bundle["ref_round"] = np.asarray(served.refs[1], np.int32)
            self.store.spill(np_ids, bundle)
        with obs.span("eval"):
            if eng.cfg.store_eval == "sampled":
                acc = eng.executor.evaluate(
                    eng.strategy, served.merged, cohort.data.x_test,
                    cohort.data.y_test)
                assignment = served.applied
            else:
                def gather_xy(ids):
                    if self.streaming:
                        d = eng.data.gather_clients(ids)
                        return d.x_test, d.y_test
                    jids = jnp.asarray(ids)
                    return eng.data.x_test[jids], eng.data.y_test[jids]

                acc = executors.evaluate_population(
                    eng.executor, eng.strategy,
                    lambda ids: jax.tree.map(jnp.asarray,
                                             self.store.gather(ids)["cs"]),
                    gather_xy, eng.n, eng.cfg.store_eval_chunk)
                assignment = self.assignment(jnp.asarray(np_ids),
                                             served.applied)
            acc = jnp.asarray(np.asarray(acc))
            # the rows live in the store: the state keeps its placeholders
            new_state = eng.next_state(
                state, served._replace(refs=(state.ref_vecs,
                                             state.ref_round)),
                state.client_state, ef)
            obs.fence(acc)
        return new_state, acc, assignment
