"""Sync federated rounds through ``Engine.run_round``, timed over a
window.

Set-up builds one engine and its state from the seed and drives the
first ``check_rounds`` rounds through the window's own call: they warm
up every shape the window uses and are what the plain reference
follows.  The same engine and state then run the window (``--trace 0``,
telemetry off): ``round_s`` is the window's length over the rounds it
completed, each closed by ``block_until_ready``, and the window ends
with the first round that ends past ``--seconds``.  ``--trace 1`` runs
instead a profiler trace over a few rounds (telemetry off, each call
inside a ``TraceAnnotation``), then a few rounds with the engine's
phase spans on.  Last, with the program's state freed, the reference
recomputes the checked rounds from the seed.
"""
from __future__ import annotations

import gc
import time

import counts
import harness
import scenario
import trace_reduce

ANNOTATION = "bench.run_round"


def _snapshot(state, rep, model: str) -> dict:
    import numpy as np
    if model == "mlp":
        out = {"server": np.asarray(state.server.slots[0], np.float64)}
    else:
        cs = state.client_state
        out = {"ta": np.asarray(cs.ta_state), "w": np.asarray(cs.weights),
               "server": np.asarray(state.server.slots, np.float32)}
    if rep is not None:
        out["acc"] = np.asarray(rep.per_client_accuracy, np.float64)
    return out


def work_per_round(cfg: dict) -> dict:
    """Operations a round requires, and the epoch kernel's least work."""
    K, N = cfg["clients_per_round"], cfg["population"]
    if cfg["model"] == "mlp":
        return {"round_ops": counts.mlp_round_flops(
            K, N, cfg["local_epochs"], cfg["n_train"], cfg["batch"],
            cfg["n_test"], cfg["n_features"], cfg["n_hidden"],
            cfg["n_classes"])}
    C, m, L = cfg["n_classes"], cfg["n_clauses"], 2 * cfg["n_features"]
    E, S = cfg["local_epochs"], cfg["n_train"]
    return {"round_ops": counts.tm_round_ops(
                K, N, E, S, cfg["n_conf"], cfg["n_test"], C, m, L),
            "kernels": {"tm_train_epoch_fused": (
                counts.tm_train_ops(K, E, S, m, L),
                counts.tm_train_bytes(K, E, S, C, m, L))}}


class Setup:
    """One engine and its state, driven from the seed through the checked
    rounds; ``snaps`` holds what the reference is compared with."""

    def __init__(self, ctx: harness.Context):
        import jax
        import jax.numpy as jnp
        from repro.fl.runtime import (CodecConfig, Engine, RuntimeConfig,
                                      SchedulerConfig)

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
            tm_backend=cfg.get("tm_backend", "ref"))
        self.engine = Engine(scenario.strategy(cfg), self.data, rt)
        if self.engine.scheduler.k != cfg["clients_per_round"]:
            raise ValueError(
                f"engine samples {self.engine.scheduler.k} clients, the "
                f"configuration {cfg['clients_per_round']}")
        self.key = jnp.asarray(harness.key_data(ctx.seed))
        k_init, self.k_rounds = jax.random.split(self.key)
        self.state = jax.block_until_ready(self.engine.init(k_init))
        parts["state_s"] = time.perf_counter() - t

        # the checked rounds: the window's own call, from the seed.  The
        # TM reference rebuilds its own initial state; the MLP's change
        # norms need the initial global model.
        t = time.perf_counter()
        model = cfg["model"]
        self.snaps = [_snapshot(self.state, None, model)
                      if model == "mlp" else {}]
        for r in range(tr["check_rounds"]):
            self.state, rep = self.engine.run_round(
                self.state, jax.random.fold_in(self.k_rounds, r))
            self.snaps.append(_snapshot(self.state, rep, model))
        self.r = tr["check_rounds"]
        parts["check_rounds_s"] = time.perf_counter() - t

    def inputs(self) -> dict:
        """The client data on the host, as the reference takes it."""
        import jax
        return {k: jax.device_get(getattr(self.data, k)) for k in
                ("x_train", "y_train", "x_test", "y_test", "x_conf")}


def readings(ctx: harness.Context, snaps: list,
             inputs: dict) -> dict[str, float]:
    """Compare snapshots of the checked rounds with the reference's."""
    import jax.numpy as jnp
    cfg = ctx.config
    ref = harness.load_module("reference", cfg["reference"])
    key = jnp.asarray(harness.key_data(ctx.seed))
    want = ref.run_rounds(key, inputs, cfg, cfg["clients_per_round"],
                          ctx.traffic["check_rounds"])
    return ref.compare(snaps, want, cfg)


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

    records: dict = {"work": work_per_round(cfg), "peak": cfg["peak"],
                     "device_kind": jax.devices()[0].device_kind}
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
                                      list(records["work"].get("kernels",
                                                               {})))
        red["calls"] = n
        harness.take_trace(outcome, red)
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
    got = readings(ctx, snaps, inputs)
    limits = cfg["limits"]["round"]
    outcome.checks = [harness.Check(k, got[k], limits[k]) for k in limits]
    return outcome

