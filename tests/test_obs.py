"""Telemetry plane tests (repro.fl.obs): serialization safety, derived
gauges, span accounting, the run-dir artifact pair, the summarizer, and
the end-to-end CLI wiring.

The bit-parity neutrality contract itself (obs-on == obs-off across
both backends and both aggregation modes) lives in
``tests/test_fl_conformance.py`` next to the rest of the parity matrix;
this file covers the obs layer's own behaviour.
"""
import dataclasses
import io
import json
import pathlib

import jax
import numpy as np
import pytest

from repro.core import tm
from repro.data import partition, synthetic
from repro.fl import masked_collectives, obs
from repro.fl.obs import events as ev
from repro.fl.obs.summarize import main as obs_cli_main
from repro.fl.obs.tracer import NullTracer, PhaseTracer
from repro.fl.runtime import (CodecConfig, Engine, RuntimeConfig, Scheduler,
                              SchedulerConfig, TPFLStrategy, checkpointing)

TM_CFG = tm.TMConfig(n_classes=10, n_clauses=20, n_features=100,
                     n_states=63, s=5.0, T=20)
N_CLIENTS = 8


@pytest.fixture(scope="module")
def data():
    x, y, dcfg = synthetic.make_dataset("synthmnist", 1500,
                                        jax.random.PRNGKey(0), side=10)
    return partition.partition(
        x, y, dcfg.n_classes, n_clients=N_CLIENTS, experiment=5,
        key=jax.random.PRNGKey(1), n_train=40, n_test=20, n_conf=20)


class _FakeReport:
    """Duck-typed RoundReport for event-derivation unit tests, loaded
    with numpy types that plain ``json`` refuses to serialize."""

    def __init__(self, n=8, j=2, n_slots=4, round_idx=0, assignment=None):
        rng = np.random.default_rng(round_idx)
        self.round_idx = np.int64(round_idx)
        self.per_client_accuracy = rng.uniform(0.3, 1.0, n).astype(
            np.float32)
        self.mean_accuracy = np.float32(self.per_client_accuracy.mean())
        self.assignment = (np.asarray(assignment) if assignment is not None
                           else rng.integers(-1, n_slots, (n, j)))
        counts = np.zeros(n_slots, np.int64)
        flat = self.assignment[self.assignment >= 0]
        np.add.at(counts, flat, 1)
        self.cluster_counts = counts
        self.upload_bytes = np.int64(12345)
        self.download_bytes_broadcast = np.int64(678)
        self.download_bytes_per_client = np.int64(90)
        self.aggregated_uploads = np.int64(n)
        self.buffered_uploads = np.int64(0)
        self.evicted_uploads = np.int64(0)
        self.participation = None


# ---------------------------------------------------------------------------
# serialization: numpy/int64-safe JSONL round-trip
# ---------------------------------------------------------------------------

def test_to_jsonable_coerces_numpy_and_nonfinite():
    raw = {
        "i64": np.int64(2 ** 40), "f32": np.float32(0.5),
        "bool": np.bool_(True), "arr": np.arange(3, dtype=np.int64),
        "nested": [np.float64("nan"), np.float64("inf"), 1.5],
        "path": pathlib.Path("/tmp/x"), "none": None, "s": "ok",
        np.int64(7): "numpy key",
    }
    out = ev.to_jsonable(raw)
    # everything is now plain-JSON: a dumps/loads round-trip is lossless
    assert json.loads(json.dumps(out)) == out
    assert out["i64"] == 2 ** 40 and isinstance(out["i64"], int)
    assert out["bool"] is True
    assert out["arr"] == [0, 1, 2]
    assert out["nested"] == [None, None, 1.5]   # NaN/inf have no JSON
    assert out["path"] == "/tmp/x"
    assert out["7"] == "numpy key"


def test_round_event_jsonl_roundtrip_with_numpy_payload(tmp_path):
    """The satellite contract: an event built from a numpy-laden report
    (int64 counters, float32 accuracies) appends as valid JSONL and
    reads back equal to its jsonable form."""
    path = tmp_path / "events.jsonl"
    written = []
    prev = None
    for r in range(3):
        rep = _FakeReport(round_idx=r)
        event = ev.round_event(rep, spans={"round": np.float64(0.25)},
                               prev_assignment=prev)
        written.append(ev.append_event(path, event))
        prev = rep.assignment
    back = ev.read_events(path)
    assert back == written
    assert [e["round"] for e in back] == [0, 1, 2]
    assert all(e["schema"] == ev.SCHEMA_VERSION for e in back)
    assert back[0]["cluster"]["churn_vs_prev"] is None      # no prev yet
    assert back[1]["cluster"]["churn_vs_prev"] is not None
    assert back[0]["bytes"]["upload"] == 12345


# ---------------------------------------------------------------------------
# derived gauges
# ---------------------------------------------------------------------------

def test_accuracy_deciles_and_worst_decile_mean():
    acc = np.arange(1, 21, dtype=np.float64) / 20.0       # 0.05 .. 1.0
    dec = ev.accuracy_deciles(acc)
    assert len(dec) == 11
    assert dec[0] == pytest.approx(0.05)                  # worst client
    assert dec[-1] == pytest.approx(1.0)                  # best client
    assert dec == sorted(dec)
    # worst decile of 20 clients = the 2 worst
    assert ev.worst_decile_mean(acc) == pytest.approx((0.05 + 0.10) / 2)
    # a single client is its own worst decile
    assert ev.worst_decile_mean([0.7]) == pytest.approx(0.7)


def test_cluster_gauges_churn_occupancy_retention():
    a0 = np.array([[0, 1], [0, -1], [2, -1], [1, 0]])
    rep = _FakeReport(n=4, j=2, n_slots=4, assignment=a0)
    rep.per_client_accuracy = np.array([1.0, 0.5, 0.25, 0.75])
    g = ev._cluster_gauges(rep, prev_assignment=None)
    assert g["occupancy"] == [3, 2, 1, 0]                 # per-slot clients
    assert g["slot_accuracy"][0] == pytest.approx((1.0 + 0.5 + 0.75) / 3)
    assert g["slot_accuracy"][3] is None                  # empty slot
    assert g["empty_slot_retention_rate"] == pytest.approx(1 / 4)
    assert g["churn_vs_prev"] is None
    # one of four clients changes a slot → churn 0.25
    a1 = a0.copy()
    a1[2, 0] = 3
    rep1 = _FakeReport(n=4, j=2, n_slots=4, assignment=a1)
    g1 = ev._cluster_gauges(rep1, prev_assignment=a0)
    assert g1["churn_vs_prev"] == pytest.approx(0.25)


def test_participation_summary_counts_are_consistent():
    sched = Scheduler(SchedulerConfig(participation=0.5, dropout=0.25,
                                      straggler=0.5, max_staleness=3),
                      n_clients=32)
    part = sched.sample(0, jax.random.PRNGKey(0))
    s = part.summary()
    active = np.asarray(part.active)
    assert s["sampled"] == active.shape[0]
    assert s["dropped"] == int((~active).sum())
    assert s["arrived_on_time"] + s["stragglers"] == int(active.sum())
    assert sum(s["staleness_hist"]) == int(active.sum())
    json.dumps(s)                                         # plain types


def test_collective_payload_bytes_formulae():
    # gather ships every upload row: 4 bytes * uploads * dim
    assert masked_collectives.collective_payload_bytes(
        "gather", n_uploads=16, dim=100, n_clusters=10) == 4 * 16 * 100
    # psum ships the (sum, count) accumulators: 4 * clusters * (dim+1)
    assert masked_collectives.collective_payload_bytes(
        "psum", n_uploads=16, dim=100, n_clusters=10) == 4 * 10 * 101
    with pytest.raises(ValueError):
        masked_collectives.collective_payload_bytes("allgather", 1, 1, 1)


# ---------------------------------------------------------------------------
# tracer: span accounting
# ---------------------------------------------------------------------------

def test_phase_tracer_accumulates_discards_and_drains():
    tr = PhaseTracer()
    with tr.span("a"):
        pass
    with tr.span("a"):                                    # re-entry adds
        pass
    with tr.span("b"):
        pass
    with tr.span("vacuous"):
        pass
    tr.discard("vacuous")
    spans = tr.take()
    assert set(spans) == {"a", "b"}
    assert all(v >= 0.0 for v in spans.values())
    assert tr.take() == {}                                # drained


def test_null_tracer_is_inert():
    tr = NullTracer()
    assert tr.enabled is False
    with tr.span("x"):
        pass
    tr.fence(np.zeros(3), None)
    tr.discard("x")
    assert tr.take() == {}
    assert obs.NULL.manifest is None
    obs.NULL.on_round(object())                           # no-op, no raise
    obs.NULL.close()


# ---------------------------------------------------------------------------
# tracer: fences, the profiler's clock, the compiles of each stage
# ---------------------------------------------------------------------------

ENGINE_STAGES = {"schedule", "gather", "broadcast_encode", "client_step",
                 "uplink_codec", "aggregate", "server_update", "downlink",
                 "apply_merge", "ref_track", "eval"}


def test_fence_is_the_tracers_choice(monkeypatch):
    blocked = []
    monkeypatch.setattr(jax, "block_until_ready", blocked.append)
    PhaseTracer(fence=False).fence(np.zeros(2))
    assert blocked == []
    PhaseTracer().fence(np.zeros(2), None)
    assert len(blocked) == 1


def test_unfenced_tracer_puts_stages_on_the_profiler_clock(tmp_path, data):
    """A CPU profiler capture of in-process rounds under an unfenced
    recorder: the host plane holds every engine stage as an
    ``engine.<stage>`` event inside its round's ``engine.round`` event,
    each with the round index as its ``round`` stat."""
    rec = obs.RunRecorder(fence=False)
    engine = Engine(TPFLStrategy(TM_CFG, local_epochs=1), data,
                    RuntimeConfig(rounds=2), telemetry=rec)
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.run(jax.random.PRNGKey(0))
    finally:
        jax.profiler.stop_trace()
        rec.close()
    (path,) = tmp_path.rglob("*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(path))
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("engine.")]
    rounds = {st["round"]: (lo, hi) for name, lo, hi, st in spans
              if name == "engine.round"}
    assert sorted(rounds) == [0, 1]
    for r, (lo, hi) in rounds.items():
        inside = {name[len("engine."):] for name, s, e, st in spans
                  if name != "engine.round" and lo <= s and e <= hi
                  and st["round"] == r}
        assert inside >= ENGINE_STAGES
        assert set(rec.history[r]["phases"]) - {"round"} <= inside


def test_compiles_are_charged_to_the_innermost_open_span():
    tr = PhaseTracer(fence=False)
    x = jax.numpy.arange(5.0)
    f = jax.jit(lambda v: v * 3.0 + 1.0)
    with tr.span("outer"):
        with tr.span("x"):
            f(x).block_until_ready()
    first = tr.take()
    assert first.compiles["x"] == 1 and first.compile_s["x"] > 0.0
    assert first.compiles.get("outer", 0) == 0
    with tr.span("x"):
        f(x).block_until_ready()                      # cached: no compile
    again = tr.take()
    assert again.compiles == {} and again.compile_s == {}
    jax.jit(lambda v: v - 2.0)(x).block_until_ready()  # outside any span
    assert tr.take().compiles == {"(none)": 1}
    tr.close()
    with tr.span("x"):
        jax.jit(lambda v: v + 5.0)(x).block_until_ready()
    assert tr.take().compiles == {}


def test_tracer_compiles_match_a_separate_listener_over_a_round(data):
    from repro.fl.obs import tracer as tracer_mod
    seen = {"n": 0, "s": 0.0}

    def listener(event, duration, **kwargs):
        if event in tracer_mod.COMPILE_EVENTS:
            seen["s"] += duration
            seen["n"] += event == tracer_mod.BACKEND_COMPILE

    jax.monitoring.register_event_duration_secs_listener(listener)
    rec = obs.RunRecorder(fence=False)
    try:
        # fresh sizes, so the round compiles its programs
        cfg = dataclasses.replace(TM_CFG, n_clauses=14)
        Engine(TPFLStrategy(cfg, local_epochs=1), data,
               RuntimeConfig(rounds=1), telemetry=rec
               ).run(jax.random.PRNGKey(0))
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
        rec.close()
    (event,) = rec.history
    assert seen["n"] >= 1
    assert sum(event["compiles"].values()) == seen["n"]
    assert sum(event["compile_s"].values()) == pytest.approx(seen["s"])
    assert set(event["compiles"]) <= set(event["phases"]) | {"(none)"}


def test_summary_shows_the_compiles_of_each_stage(tmp_path):
    path = tmp_path / "events.jsonl"
    for r in range(2):
        ev.append_event(path, ev.round_event(
            _FakeReport(round_idx=r),
            spans={"round": 1.0, "client_step": 0.6, "apply_merge": 0.2},
            compiles={"apply_merge": 1, "(none)": r},
            compile_s={"apply_merge": 0.05, "(none)": 0.01 * r}))
    events = ev.read_events(path)
    assert events[1]["compiles"] == {"apply_merge": 1, "(none)": 1}
    assert obs.phase_compiles(events) == {"apply_merge": 2, "(none)": 1}
    buf = io.StringIO()
    obs.summarize(tmp_path, out=buf)
    rows = {ln.split()[0]: ln.split() for ln in buf.getvalue().splitlines()
            if ln.strip()}
    assert rows["phase"][-1] == "compiles"
    assert rows["apply_merge"][-1] == "2"
    assert rows["client_step"][-1] == "0"
    assert rows["(none)"][-1] == "1"


# ---------------------------------------------------------------------------
# recorder + engine integration
# ---------------------------------------------------------------------------

def test_recorder_run_dir_holds_manifest_and_events(tmp_path, data):
    run_dir = tmp_path / "run"
    cfg = RuntimeConfig(rounds=2)
    rec = obs.RunRecorder(run_dir=run_dir)
    rec.start(obs.build_manifest(config=cfg, seed=0,
                                 extra={"strategy": "tpfl"}))
    engine = Engine(TPFLStrategy(TM_CFG, local_epochs=1), data, cfg,
                    telemetry=rec)
    engine.run(jax.random.PRNGKey(0))
    rec.close()

    manifest = obs.read_manifest(run_dir)
    assert manifest["seed"] == 0
    assert manifest["strategy"] == "tpfl"
    assert manifest["config"]["aggregation"] == "sync"
    assert manifest["config"]["scheduler"]["participation"] == 1.0
    assert manifest["jax_version"] == jax.__version__

    events = ev.read_events(run_dir / "events.jsonl")
    assert len(events) == 2 == len(rec.history)
    assert events == rec.history
    for e in events:
        assert e["accuracy"]["deciles"][0] <= e["accuracy"]["mean"]
        assert e["scheduler"]["sampled"] == N_CLIENTS
        assert e["phases"]["client_step"] > 0.0


def test_phase_spans_sum_to_round_total(data):
    """Acceptance criterion: the per-phase wall times approximately
    account for the whole round — fences bill device work to the stage
    that launched it, so the stage sum can't be a sliver of the total."""
    rec = obs.RunRecorder()                               # in-memory
    engine = Engine(TPFLStrategy(TM_CFG, local_epochs=1), data,
                    RuntimeConfig(rounds=3), telemetry=rec)
    engine.run(jax.random.PRNGKey(0))
    for e in rec.history:
        phases = e["phases"]
        total = phases["round"]
        stages = sum(v for k, v in phases.items() if k != "round")
        assert stages <= total * 1.05                     # no double-billing
        assert stages >= total * 0.5                      # ...and no gaps


def test_async_round_records_buffer_phases(data):
    rec = obs.RunRecorder()
    cfg = RuntimeConfig(rounds=2, aggregation="async", async_min_uploads=2,
                        scheduler=SchedulerConfig(straggler=0.5,
                                                  max_staleness=2))
    Engine(TPFLStrategy(TM_CFG, local_epochs=1), data, cfg,
           telemetry=rec).run(jax.random.PRNGKey(0))
    for e in rec.history:
        assert "aggregate" in e["phases"]
        asy = e["async"]
        assert asy["aggregated"] >= 0 and asy["buffered"] >= 0


def test_checkpoint_carries_manifest_ride_along(tmp_path, data):
    cfg = RuntimeConfig(rounds=2, checkpoint_dir=str(tmp_path / "ck"),
                        checkpoint_every=1)
    rec = obs.RunRecorder()
    rec.start(obs.build_manifest(config=cfg, seed=0))
    engine = Engine(TPFLStrategy(TM_CFG, local_epochs=1), data, cfg,
                    telemetry=rec)
    engine.run(jax.random.PRNGKey(0))
    ck_manifest = json.loads(
        (tmp_path / "ck" / checkpointing.MANIFEST_NAME).read_text())
    assert ck_manifest["seed"] == 0
    assert ck_manifest["config"]["rounds"] == 2
    # restore ignores the provenance file and still works
    like = engine.init(jax.random.PRNGKey(0))
    restored = checkpointing.restore(
        checkpointing.latest(tmp_path / "ck"), like)
    assert restored is not None


# ---------------------------------------------------------------------------
# summarizer + CLI
# ---------------------------------------------------------------------------

def _telemetry_run(tmp_path, data, rounds=2):
    run_dir = tmp_path / "run"
    cfg = RuntimeConfig(rounds=rounds)
    rec = obs.RunRecorder(run_dir=run_dir)
    rec.start(obs.build_manifest(config=cfg, seed=0,
                                 extra={"strategy": "tpfl",
                                        "dataset": "synthmnist"}))
    Engine(TPFLStrategy(TM_CFG, local_epochs=1), data, cfg,
           telemetry=rec).run(jax.random.PRNGKey(0))
    rec.close()
    return run_dir


def test_summarize_renders_run_dir(tmp_path, data):
    run_dir = _telemetry_run(tmp_path, data)
    buf = io.StringIO()
    out = obs.summarize(run_dir, out=buf)
    assert len(out["events"]) == 2
    text = buf.getvalue()
    assert "strategy=tpfl" in text
    assert "client_step" in text                          # phase table
    assert "worst-decile mean" in text                    # decile table
    assert "round total" in text


def test_summarize_refuses_non_run_dir(tmp_path):
    with pytest.raises(SystemExit, match="events.jsonl"):
        obs.summarize(tmp_path)


def test_obs_cli_main_smoke(tmp_path, data, capsys):
    run_dir = _telemetry_run(tmp_path, data)
    assert obs_cli_main(["summarize", str(run_dir)]) == 0
    assert "per-phase wall time" in capsys.readouterr().out


def test_fed_train_telemetry_dir_end_to_end(tmp_path):
    from repro.launch import fed_train
    run_dir = tmp_path / "run"
    out = fed_train.main(["--strategy", "tpfl", "--clients", "6",
                          "--rounds", "2", "--local-epochs", "1",
                          "--telemetry-dir", str(run_dir)])
    assert len(out["acc_per_round"]) == 2
    assert len(out["final_accuracy_deciles"]) == 11
    manifest = obs.read_manifest(run_dir)
    assert manifest["strategy"] == "tpfl"
    assert manifest["rounds"] == 2
    events = ev.read_events(run_dir / "events.jsonl")
    assert len(events) == 2
    # the events' metered bytes match the CLI's own totals
    assert sum(e["bytes"]["upload"] for e in events) == out["upload_bytes"]
    buf = io.StringIO()
    obs.summarize(run_dir, out=buf)
    assert "rounds: 2" in buf.getvalue()


# ---------------------------------------------------------------------------
# the round's span sequence, pinned per round shape
# ---------------------------------------------------------------------------

class _SpanLog(PhaseTracer):
    """A tracer that also logs every span it opens, indented by its
    nesting depth, and every discard as ``-name``."""

    def __init__(self):
        super().__init__()
        self.log = []

    def span(self, name):
        self.log.append("  " * len(self._stack) + name)
        return super().span(name)

    def discard(self, name):
        self.log.append("-" + name)
        super().discard(name)

    def on_round(self, report):
        pass


_STAGED = ["round", "  schedule", "  gather", "  broadcast_encode",
           "  client_step", "  uplink_codec", "  aggregate",
           "  server_update", "  downlink", "  apply_merge", "  ref_track"]
SPAN_SEQUENCES = {
    "staged-resident": _STAGED + ["  eval"],
    "staged-identity": _STAGED[:3] + ["  fused_round", "-fused_round"]
    + _STAGED[3:] + ["  eval"],
    "staged-mmap": _STAGED + ["  spill", "  eval"],
    "fused-shardmap": ["round", "  schedule", "  gather", "  fused_round",
                       "  downlink", "  eval"],
    "loopback-sync": ["round", "  schedule", "  broadcast_encode",
                      "  wire_tx", "  wire_rx", "  uplink_codec",
                      "  aggregate", "  server_update", "  downlink",
                      "  ref_track", "  wire_tx", "  eval", "    wire_rx"],
}


@pytest.mark.parametrize("shape", sorted(SPAN_SEQUENCES))
def test_round_span_sequence_is_pinned(shape, data, tmp_path):
    """One round under each of the round's shapes opens exactly these
    spans, in this order and nesting: the stage names the benchmark and
    the profiler-trace reader key on."""
    from repro.fl.transport import TransportEngine
    from repro.launch.mesh import make_clients_mesh
    log = _SpanLog()
    strat = TPFLStrategy(TM_CFG, local_epochs=1)
    if shape == "loopback-sync":
        cfg = RuntimeConfig(rounds=1, transport="loopback", workers=2)
        TransportEngine(strat, data, cfg, telemetry=log).run(
            jax.random.PRNGKey(0))
    else:
        kw = {"staged-resident": dict(scheduler=SchedulerConfig(
                  participation=0.5)),
              "staged-mmap": dict(
                  client_store="mmap", store_dir=str(tmp_path / "store"),
                  codec=CodecConfig("int8", sparse=True),
                  scheduler=SchedulerConfig(participation=0.5)),
              "staged-identity": {},
              "fused-shardmap": dict(backend="shardmap")}[shape]
        mesh = make_clients_mesh() if shape == "fused-shardmap" else None
        Engine(strat, data, RuntimeConfig(rounds=1, **kw), mesh=mesh,
               telemetry=log).run(jax.random.PRNGKey(0))
    log.close()
    assert log.log == SPAN_SEQUENCES[shape]
