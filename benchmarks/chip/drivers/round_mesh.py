"""Sync federated rounds of the shard-mapped engine on a ``clients``
mesh, timed over a window.

The engine is ``Engine(..., RuntimeConfig(backend="shardmap",
mesh_collective=<collective>), mesh=make_clients_mesh(<clients>))``
with the traffic's ``mesh`` and ``collective``: the population, its data
and its training split over the chips, one block of clients a chip, the
server replicated, the aggregation one collective inside the round's one
program.  The rest is ``drivers/round.py``'s discipline, and its
``_snapshot``, ``readings`` and ``work_per_round``: set-up drives the
checked rounds from the seed through the window's own call; ``--trace
0`` times the window (``round_s``); ``--trace 1`` takes a profiler trace
over a few rounds, then a few rounds with the engine's phase spans on;
last, with the program's state freed, the plain reference recomputes
the checked rounds on one chip.

Per-chip readings: ``work`` is one chip's share of a round,
``work_per_round`` with ``clients_per_round`` and ``population`` divided
by the chips, so that ``trace_reduce``'s averages over the device
planes, ``mfu.train`` and ``tm_train_epoch_fused_roofline`` read one
chip against one chip's peak.  Part 1 also keeps the device time under
the scope ``mesh.collective`` (the masked collective, mean over the
chips; ``records["scopes"]``), and the records hold the engine's
per-device payload gauge of that collective (``collective_bytes``).
"""
from __future__ import annotations

import gc
import time

import harness
import scenario
import trace_reduce
import trace_scopes

ANNOTATION = "bench.run_round"
SCOPE = "mesh.collective"

_round = harness.load_module("drivers", "round")


def work_per_chip(cfg: dict, chips: int) -> dict:
    """``work_per_round`` of one chip's block of the cohort and of the
    population."""
    return _round.work_per_round(
        dict(cfg, clients_per_round=cfg["clients_per_round"] // chips,
             population=cfg["population"] // chips))


class Setup:
    """One shard-mapped engine and its state, driven from the seed
    through the checked rounds; ``snaps`` holds what the reference is
    compared with."""

    def __init__(self, ctx: harness.Context):
        import jax
        import jax.numpy as jnp
        from repro.fl.runtime import (CodecConfig, Engine, RuntimeConfig,
                                      SchedulerConfig)
        from repro.launch.mesh import make_clients_mesh

        cfg, tr = ctx.config, ctx.traffic
        parts = ctx.setup_parts
        t = time.perf_counter()
        self.data = scenario.client_data(ctx)
        parts["data_s"] = time.perf_counter() - t

        t = time.perf_counter()
        rt = RuntimeConfig(
            rounds=1,
            scheduler=SchedulerConfig(
                participation=cfg["clients_per_round"] / cfg["population"],
                sampling=tr["sampling"]),
            codec=CodecConfig(tr["codec"]), aggregation=tr["mode"],
            backend="shardmap", mesh_collective=tr["collective"],
            tm_backend=cfg.get("tm_backend", "ref"))
        self.engine = Engine(scenario.strategy(cfg), self.data, rt,
                             mesh=make_clients_mesh(tr["mesh"]["clients"]))
        if self.engine.scheduler.k != cfg["clients_per_round"]:
            raise ValueError(
                f"engine samples {self.engine.scheduler.k} clients, the "
                f"configuration {cfg['clients_per_round']}")
        key = jnp.asarray(harness.key_data(ctx.seed))
        k_init, self.k_rounds = jax.random.split(key)
        self.state = jax.block_until_ready(self.engine.init(k_init))
        parts["state_s"] = time.perf_counter() - t

        t = time.perf_counter()
        self.snaps = [{}]
        for r in range(tr["check_rounds"]):
            self.state, rep = self.engine.run_round(
                self.state, jax.random.fold_in(self.k_rounds, r))
            self.snaps.append(_round._snapshot(self.state, rep,
                                               cfg["model"]))
        self.r = tr["check_rounds"]
        parts["check_rounds_s"] = time.perf_counter() - t

    def inputs(self) -> dict:
        """The client data on the host, as the reference takes it."""
        import jax
        return {k: jax.device_get(getattr(self.data, k)) for k in
                ("x_train", "y_train", "x_test", "y_test", "x_conf")}


def run(ctx: harness.Context) -> harness.Outcome:
    import jax
    from repro.fl import obs

    cfg, tr = ctx.config, ctx.traffic
    s = Setup(ctx)
    engine, state, k_rounds, r = s.engine, s.state, s.k_rounds, s.r
    setup_s = time.perf_counter() - ctx.t0

    def one_round(state, r):
        state, rep = engine.run_round(state, jax.random.fold_in(k_rounds, r))
        jax.block_until_ready((state, rep.per_client_accuracy))
        return state, rep

    records: dict = {"work": work_per_chip(cfg, tr["mesh"]["clients"]),
                     "peak": cfg["peak"],
                     "device_kind": jax.devices()[0].device_kind,
                     "collective_bytes": engine.collective_payload_bytes()}
    outcome = harness.Outcome(attempted=0, failed=0, end_to_end={},
                              records=records, checks=[],
                              memory_peak_bytes=0)
    wire = []
    if not ctx.trace:
        with harness.CompileCounter() as compiles:
            t_start = time.perf_counter()
            n = 0
            while True:
                state, rep = one_round(state, r)
                r, n = r + 1, n + 1
                wire.append(rep.upload_bytes + rep.download_bytes_per_client)
                if time.perf_counter() - t_start >= ctx.seconds:
                    break
            elapsed = time.perf_counter() - t_start
        ctx.setup_parts["window_compiles"] = compiles.n
        outcome.end_to_end = {"setup_s": setup_s, "round_s": elapsed / n}
        outcome.attempted = n
    else:
        # part 1: the device trace, telemetry off
        tdir = ctx.work_dir / "trace"
        trace_reduce.start(tdir)
        t_start = time.perf_counter()
        n = 0
        while n < tr["trace_rounds"] or \
                time.perf_counter() - t_start < tr["trace_seconds"]:
            with jax.profiler.TraceAnnotation(ANNOTATION):
                state, rep = one_round(state, r)
            r, n = r + 1, n + 1
            wire.append(rep.upload_bytes + rep.download_bytes_per_client)
        jax.profiler.stop_trace()
        red = trace_reduce.reduce_dir(tdir, ANNOTATION,
                                      list(records["work"]["kernels"]))
        red["calls"] = n
        harness.take_trace(outcome, red)
        records["scopes"] = trace_scopes.reduce_dir(tdir, ANNOTATION,
                                                    [SCOPE])
        # part 2: the engine's phase spans (fenced), as Engine.run times
        rec = obs.RunRecorder()
        engine.obs = rec
        spans = []
        for _ in range(tr["span_rounds"]):
            with rec.span("round"):
                state, rep = engine.run_round(
                    state, jax.random.fold_in(k_rounds, r))
                rec.fence(state)
            spans.append(rec.take())
            r, n = r + 1, n + 1
        engine.obs = obs.NULL
        records["spans"] = spans
        outcome.attempted = n
    records["wire_bytes"] = wire
    outcome.memory_peak_bytes = harness.memory_peak_bytes()

    # the reference, once the program's state is freed
    inputs, snaps = s.inputs(), s.snaps
    del s, engine, state, rep
    gc.collect()
    got = _round.readings(ctx, snaps, inputs)
    limits = cfg["limits"]["round"]
    outcome.checks = [harness.Check(k, got[k], limits[k]) for k in limits]
    return outcome
