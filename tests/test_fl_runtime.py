"""Federated runtime system tests: codec, scheduler, engine, resume."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import federation, tm
from repro.data import partition, synthetic
from repro.fl import masked_collectives
from repro.fl.runtime import (CodecConfig, Engine, FedAvgStrategy,
                              IFCAStrategy, RuntimeConfig, Scheduler,
                              SchedulerConfig, TPFLStrategy, checkpointing,
                              codec)

TM_CFG = tm.TMConfig(n_classes=10, n_clauses=20, n_features=100,
                     n_states=63, s=5.0, T=20)


def _data(n_clients=8, experiment=5, seed=0):
    x, y, dcfg = synthetic.make_dataset("synthmnist", 1500,
                                        jax.random.PRNGKey(seed), side=10)
    return partition.partition(
        x, y, dcfg.n_classes, n_clients=n_clients, experiment=experiment,
        key=jax.random.PRNGKey(seed + 1), n_train=40, n_test=20, n_conf=20)


def _tpfl_engine(data, rt_cfg, local_epochs=1):
    strat = TPFLStrategy(TM_CFG, local_epochs=local_epochs)
    return Engine(strat, data, rt_cfg)


# ---------------------------------------------------------------------------
# wire codec
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", codec.CODECS)
@pytest.mark.parametrize("sparse", [False, True])
def test_codec_roundtrip_within_quantization_tolerance(name, sparse):
    rng = np.random.default_rng(0)
    vec = rng.normal(scale=30.0, size=64).astype(np.float32)
    ref = rng.normal(scale=30.0, size=64).astype(np.float32)
    cfg = CodecConfig(name, sparse=sparse)
    buf = codec.encode(vec, cfg, ref=ref)
    out = codec.decode(buf, 64, cfg, ref=ref)
    tol = codec.roundtrip_tolerance(vec - ref if sparse else vec, cfg)
    assert np.abs(out - vec).max() <= tol + 1e-6
    if name == "float32" and not sparse:
        assert (out == vec).all()           # legacy wire format: bit-exact


def test_codec_dense_frame_sizes_exact():
    m = 33
    vec = np.linspace(-5, 5, m).astype(np.float32)
    assert len(codec.encode(vec, CodecConfig("float32"))) == 4 * m
    assert len(codec.encode(vec, CodecConfig("int8"))) == 4 + m
    assert len(codec.encode(vec, CodecConfig("int4"))) == 4 + (m + 1) // 2


def test_codec_sparse_delta_smaller_when_delta_sparse():
    m = 256
    ref = np.arange(m, dtype=np.float32)
    vec = ref.copy()
    vec[[3, 100]] += 7.0                    # two entries changed
    cfg = CodecConfig("int8", sparse=True)
    buf = codec.encode(vec, cfg, ref=ref)
    assert len(buf) < len(codec.encode(vec, CodecConfig("int8")))
    out = codec.decode(buf, m, cfg, ref=ref)
    assert np.abs(out - vec).max() <= codec.roundtrip_tolerance(vec - ref,
                                                                cfg) + 1e-6


def test_metered_bytes_equal_encoded_buffer_length():
    """The engine's upload meter is Σ (4-byte slot id + len(frame))."""
    data = _data(n_clients=4)
    eng = _tpfl_engine(data, RuntimeConfig(
        rounds=1, codec=CodecConfig("int8")))
    _, reports = eng.run(jax.random.PRNGKey(0))
    frame = 4 + (4 + TM_CFG.n_clauses)      # id + (scale + m int8 bytes)
    assert reports[0].upload_bytes == 4 * frame


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

def test_scheduler_full_participation_is_identity():
    s = Scheduler(SchedulerConfig(), n_clients=6)
    part = s.sample(0, jax.random.PRNGKey(0))
    assert part.idx.tolist() == list(range(6))
    assert bool(part.active.all()) and int(part.staleness.sum()) == 0


def test_scheduler_uniform_samples_k_distinct():
    s = Scheduler(SchedulerConfig(participation=0.25), n_clients=16)
    assert s.k == 4
    part = s.sample(3, jax.random.PRNGKey(1))
    ids = part.idx.tolist()
    assert len(set(ids)) == 4 and all(0 <= i < 16 for i in ids)


def test_scheduler_round_robin_covers_population():
    s = Scheduler(SchedulerConfig(participation=0.25,
                                  sampling="round_robin"), n_clients=8)
    seen = set()
    for r in range(4):
        seen.update(s.sample(r, jax.random.PRNGKey(r)).idx.tolist())
    assert seen == set(range(8))


def test_scheduler_straggler_staleness_bounded():
    s = Scheduler(SchedulerConfig(straggler=1.0, max_staleness=3),
                  n_clients=12)
    part = s.sample(0, jax.random.PRNGKey(2))
    st = part.staleness.tolist()
    assert all(1 <= v <= 3 for v in st)


# ---------------------------------------------------------------------------
# engine: dropout isolation (the paper's non-IID core claim)
# ---------------------------------------------------------------------------

def test_dropped_sole_member_leaves_its_cluster_untouched():
    """dropout = 1.0: every upload is lost, so every cluster — including
    any whose only member was sampled — keeps its previous weights and no
    wrongful aggregation happens."""
    data = _data()
    eng = _tpfl_engine(data, RuntimeConfig(
        rounds=1, scheduler=SchedulerConfig(dropout=1.0)))
    state = eng.init(jax.random.PRNGKey(0))
    seeded = state._replace(server=state.server._replace(
        slots=jnp.arange(TM_CFG.n_classes * TM_CFG.n_clauses,
                         dtype=jnp.float32).reshape(TM_CFG.n_classes, -1)))
    new_state, rep = eng.run_round(seeded, jax.random.PRNGKey(1))
    assert (new_state.server.slots == seeded.server.slots).all()
    assert int(rep.cluster_counts.sum()) == 0
    assert int(rep.upload_bytes) == 0
    # the dropped clients' local state is also untouched (crashed mid-round)
    assert (new_state.client_state.weights
            == seeded.client_state.weights).all()


def test_partial_participation_leaves_nonparticipants_unchanged():
    data = _data()
    eng = _tpfl_engine(data, RuntimeConfig(
        rounds=1, scheduler=SchedulerConfig(participation=0.25)))
    state = eng.init(jax.random.PRNGKey(0))
    new_state, rep = eng.run_round(state, jax.random.PRNGKey(1))
    part = set(rep.participation.idx.tolist())
    assert len(part) == 2
    for i in range(8):
        same = bool((new_state.client_state.ta_state[i]
                     == state.client_state.ta_state[i]).all())
        if i not in part:
            assert same
            assert int(rep.assignment[i, 0]) == -1


# ---------------------------------------------------------------------------
# engine: legacy reproduction + scenarios
# ---------------------------------------------------------------------------

def test_sync_full_participation_reproduces_legacy_run_round():
    data = _data()
    fed = federation.FedConfig(n_clients=8, rounds=2, local_epochs=1)
    key = jax.random.PRNGKey(0)

    k_init, k_rounds = jax.random.split(key)
    st = federation.init_state(TM_CFG, fed, k_init)
    legacy = []
    for r in range(fed.rounds):
        st, m = federation.run_round(
            st, data, jax.random.fold_in(k_rounds, r), TM_CFG, fed)
        legacy.append(m)

    st2, hist = federation.run(data, TM_CFG, fed, key)
    for a, b in zip(legacy, hist):
        assert float(a.mean_accuracy) == float(b.mean_accuracy)
        assert (a.assignment == b.assignment).all()
        assert (a.cluster_counts == b.cluster_counts).all()
        assert a.upload_bytes == b.upload_bytes
        assert a.download_bytes_broadcast == b.download_bytes_broadcast
        assert a.download_bytes_per_client == b.download_bytes_per_client
    assert (st.client_params.weights == st2.client_params.weights).all()
    assert jnp.allclose(st.cluster_weights, st2.cluster_weights)


def test_async_buffered_aggregation_applies_mature_uploads():
    data = _data()
    eng = _tpfl_engine(data, RuntimeConfig(
        rounds=3, aggregation="async", async_min_uploads=2,
        scheduler=SchedulerConfig(participation=0.5, straggler=0.5,
                                  max_staleness=2)))
    _, reports = eng.run(jax.random.PRNGKey(0))
    total_agg = sum(r.aggregated_uploads for r in reports)
    assert total_agg > 0
    assert all(r.evicted_uploads == 0 for r in reports)
    # stale uploads either matured (aggregated) or still sit in the buffer
    sent = sum(int(r.participation.active.sum()) for r in reports)
    assert total_agg + reports[-1].buffered_uploads == sent
    # the async path must not wreck the models (e.g. by broadcasting
    # never-aggregated zero slots over freshly trained clients)
    assert float(reports[-1].mean_accuracy) > 0.4


@pytest.mark.parametrize("buffer", ["device", "host"])
def test_async_below_threshold_broadcasts_nothing(buffer):
    """Rounds where the buffer stays below B must leave both the server
    and the clients' locally trained weights untouched."""
    # seed 2: a partition where one local epoch lifts every round key
    # tried (1-3) clear of the 0.5 bar under the installed PRNG stream
    data = _data(seed=2)
    eng = _tpfl_engine(data, RuntimeConfig(
        rounds=1, aggregation="async", async_min_uploads=10 ** 6,
        async_buffer=buffer))
    state = eng.init(jax.random.PRNGKey(0))
    new_state, rep = eng.run_round(state, jax.random.PRNGKey(1))
    assert rep.aggregated_uploads == 0
    assert (new_state.server.slots == state.server.slots).all()
    assert (rep.assignment == -1).all()          # nothing applied
    assert rep.download_bytes_per_client == 0    # nothing billed either
    # clients keep their local training: accuracy ≈ isolated-TM level
    assert float(rep.mean_accuracy) > 0.5


@pytest.mark.parametrize("buffer", ["device", "host"])
def test_async_overflow_evicts_oldest_insertion_first(buffer):
    """4 uploads into a capacity-2 buffer: the two oldest are evicted,
    the two newest survive."""
    data = _data()
    eng = _tpfl_engine(data, RuntimeConfig(
        rounds=1, aggregation="async", async_min_uploads=10 ** 6,
        buffer_capacity=2, async_buffer=buffer,
        scheduler=SchedulerConfig(participation=0.5)))
    state = eng.init(jax.random.PRNGKey(0))
    new_state, rep = eng.run_round(state, jax.random.PRNGKey(1))
    assert rep.evicted_uploads == 2
    assert rep.buffered_uploads == 2
    assert new_state.buf_seq.tolist() == [2, 3]      # newest insertions


@pytest.mark.parametrize("buffer", ["device", "host"])
def test_async_zero_staleness_weight_never_populates_a_slot(buffer):
    """discount=0 + every upload stale → zero aggregate weight: the
    server must keep its previous rows rather than zeroing them."""
    data = _data()
    eng = _tpfl_engine(data, RuntimeConfig(
        rounds=1, aggregation="async", async_min_uploads=1,
        staleness_discount=0.0, async_buffer=buffer,
        scheduler=SchedulerConfig(straggler=1.0, max_staleness=1)))
    state = eng.init(jax.random.PRNGKey(0))
    seeded = state._replace(server=state.server._replace(
        slots=jnp.full_like(state.server.slots, 7.0)))
    # round 0 buffers everything (staleness 1); round 1 matures them
    mid, rep0 = eng.run_round(seeded, jax.random.PRNGKey(1))
    new_state, rep1 = eng.run_round(mid, jax.random.PRNGKey(2))
    assert rep0.aggregated_uploads == 0
    assert rep1.aggregated_uploads == 0          # weight-0 ≠ contribution
    assert (new_state.server.slots == seeded.server.slots).all()
    assert (rep1.assignment == -1).all()         # nothing broadcast


@pytest.mark.parametrize("buffer", ["device", "host"])
def test_async_maturing_exactly_at_min_uploads_aggregates(buffer):
    """The maturity gate is ≥, not >: a round whose matured count lands
    exactly on ``async_min_uploads`` aggregates all of them and drains
    the buffer."""
    data = _data()   # 8 clients, full participation, j = 1 → 8 uploads
    eng = _tpfl_engine(data, RuntimeConfig(
        rounds=1, aggregation="async", async_min_uploads=8,
        async_buffer=buffer))
    state = eng.init(jax.random.PRNGKey(0))
    new_state, rep = eng.run_round(state, jax.random.PRNGKey(1))
    assert rep.aggregated_uploads == 8
    assert rep.buffered_uploads == 0
    assert not bool(np.asarray(new_state.buf_valid).any())
    # one fewer upload must NOT aggregate
    eng9 = _tpfl_engine(data, RuntimeConfig(
        rounds=1, aggregation="async", async_min_uploads=9,
        async_buffer=buffer))
    _, rep9 = eng9.run_round(eng9.init(jax.random.PRNGKey(0)),
                             jax.random.PRNGKey(1))
    assert rep9.aggregated_uploads == 0
    assert rep9.buffered_uploads == 8


def test_async_entries_can_outlive_max_staleness_ungated():
    """An upload whose maturity round has long passed (buffer age >
    max_staleness because the B-threshold never fired) must stay valid
    with its original discount weight — age in the buffer is not
    staleness, and nothing silently expires."""
    data = _data()
    eng = _tpfl_engine(data, RuntimeConfig(
        rounds=4, aggregation="async", async_min_uploads=10 ** 6,
        buffer_capacity=64,
        scheduler=SchedulerConfig(participation=0.25, straggler=1.0,
                                  max_staleness=2)))
    state = eng.init(jax.random.PRNGKey(0))
    for r in range(4):
        state, rep = eng.run_round(state, jax.random.fold_in(
            jax.random.PRNGKey(0), r))
        assert rep.aggregated_uploads == 0
    valid = np.asarray(state.buf_valid)
    ready = np.asarray(state.buf_ready)[valid]
    weight = np.asarray(state.buf_weight)[valid]
    assert valid.sum() == 4 * 2                 # K=2 per round, none lost
    # round-0 entries matured at ready ≤ 2 — two rounds “overdue” by now
    assert int(ready.min()) <= 2 < int(state.round_idx)
    assert (weight >= 0.5 ** 2 - 1e-7).all()    # discount from staleness,
    assert (weight <= 1.0).all()                # never from buffer age


@pytest.mark.parametrize("form", ["scheduled", "arrival"])
def test_host_buffer_insert_takes_free_slots_then_evicts_oldest(form):
    """The one host-buffer insert both async host paths use: free lanes
    are taken first, in order; a full buffer evicts the oldest
    *insertion* (not the oldest lane) and counts it; each entry keeps
    its own ``ready`` and ``weight``.  ``scheduled`` entries are the
    engine's injected schedule (mature at ``r + staleness``, weight
    ``discount**staleness``); ``arrival`` entries are the transport
    runner's (mature on arrival, weighted by the observed lag)."""
    from repro.fl.runtime.engine import host_buffer_insert
    cap, d, r, disc = 3, 2, 5, 0.5
    # lane 1 already holds an older upload (insertion seq 7)
    buf = (jnp.zeros((cap, d), jnp.float32).at[1].set(9.0),
           jnp.asarray([-1, 9, -1], jnp.int32),
           jnp.asarray([0, 4, 0], jnp.int32),
           jnp.asarray([0.0, 1.0, 0.0], jnp.float32),
           jnp.asarray([False, True, False]),
           jnp.asarray([0, 7, 0], jnp.int32))
    lags = [0, 1, 2, 1]
    entries = [(np.full((d,), float(i), np.float32), i,
                r + lag if form == "scheduled" else r, disc ** lag)
               for i, lag in enumerate(lags)]
    (vecs, slots, ready, weight, valid, seq), evicted = \
        host_buffer_insert(buf, entries)
    # entry 0 → free lane 0, entry 1 → free lane 2; then the buffer is
    # full: entry 2 evicts lane 1 (seq 7), entry 3 evicts lane 0 (seq 8)
    lane_of = {3: 0, 2: 1, 1: 2}
    assert evicted == 2
    assert valid.all()
    assert seq.tolist() == [11, 10, 9]
    for i, lane in lane_of.items():
        assert slots[lane] == i
        assert (vecs[lane] == float(i)).all()
        assert ready[lane] == (r + lags[i] if form == "scheduled" else r)
        assert weight[lane] == np.float32(disc ** lags[i])
    # the carried buffer is copied, never written in place
    assert np.asarray(buf[4]).tolist() == [False, True, False]


def test_client_step_consumes_codec_roundtripped_broadcast():
    """ROADMAP fix: local training must start from the broadcast rows a
    client would actually hold after a lossy downlink, not the
    aggregator's full-precision state.  Spy on the server matrix the
    engine hands the executor's train stage."""
    from repro.fl.runtime import codec as codec_mod
    data = _data(n_clients=4)
    wire = CodecConfig("int8")
    eng = Engine(FedAvgStrategy(n_features=100, n_classes=10, n_hidden=16,
                                local_epochs=1),
                 data, RuntimeConfig(rounds=1, codec=wire))
    state = eng.init(jax.random.PRNGKey(0))
    seen = {}
    orig = eng.executor.train

    def spy(strategy, cs, server, d, keys):
        seen["server"] = np.asarray(server)
        return orig(strategy, cs, server, d, keys)

    eng.executor.train = spy
    eng.run_round(state, jax.random.PRNGKey(1))

    full = np.asarray(state.server.slots, np.float32)
    dense = CodecConfig("int8")
    expect = np.stack([
        codec_mod.decode(codec_mod.encode(full[s], dense), full.shape[1],
                         dense) for s in range(full.shape[0])])
    assert (seen["server"] == expect).all()
    assert (expect != full).any()        # int8 really did lose precision


def test_wire_tx_server_is_identity_for_dense_float32():
    data = _data(n_clients=4)
    eng = _tpfl_engine(data, RuntimeConfig(rounds=1))
    server = jnp.arange(12, dtype=jnp.float32).reshape(3, 4)
    assert eng.wire_tx_server(server) is server


def test_engine_run_rounds_override_completes_remainder():
    data = _data()
    eng = _tpfl_engine(data, RuntimeConfig(rounds=3))
    key = jax.random.PRNGKey(5)
    state, reports = eng.run(key, rounds=1)
    assert len(reports) == 1 and int(state.round_idx) == 1
    state, reports = eng.run(key, state=state, rounds=2)
    assert len(reports) == 2 and int(state.round_idx) == 3


def test_checkpoint_resume_is_bit_identical(tmp_path):
    data = _data()
    key = jax.random.PRNGKey(5)
    full = _tpfl_engine(data, RuntimeConfig(rounds=4))
    state_full, reports_full = full.run(key)

    half = _tpfl_engine(data, RuntimeConfig(
        rounds=2, checkpoint_dir=str(tmp_path), checkpoint_every=2))
    half.run(key)
    ck = checkpointing.latest(tmp_path)
    assert ck is not None and "round_000002" in ck.name
    resumed = checkpointing.restore(
        ck, half.init(jax.random.PRNGKey(0)))
    state_res, reports_res = half.run(key, state=resumed)

    assert int(state_res.round_idx) == 4
    for a, b in zip(reports_full[2:], reports_res):
        assert float(a.mean_accuracy) == float(b.mean_accuracy)
        assert (a.assignment == b.assignment).all()
    assert (state_full.client_state.weights
            == state_res.client_state.weights).all()


def test_lossy_downlink_is_applied_to_clients():
    """Clients must receive the codec-roundtripped broadcast, not the
    aggregator's full-precision rows."""
    data = _data(n_clients=4)
    eng = _tpfl_engine(data, RuntimeConfig(
        rounds=1, codec=CodecConfig("int4")))
    state = eng.init(jax.random.PRNGKey(0))
    new_state, rep = eng.run_round(state, jax.random.PRNGKey(1))
    dense = CodecConfig("int4")
    checked = 0
    for i in range(4):
        s = int(rep.assignment[i, 0])
        if s < 0:
            continue
        row = np.asarray(new_state.server.slots[s], np.float32)
        rx = codec.decode(codec.encode(row, dense), TM_CFG.n_clauses,
                          dense)
        expect = np.round(rx).astype(np.int32)
        got = np.asarray(new_state.client_state.weights[i, s])
        assert (got == expect).all()
        checked += 1
    assert checked > 0


def test_conf_threshold_cuts_metered_upload_bytes():
    """Slot −1 ('nothing shared') sends no frame: §7 selective sharing
    shows up in the byte-exact meter, not just in cluster counts."""
    data = _data(n_clients=4)
    gated = Engine(TPFLStrategy(TM_CFG, local_epochs=1,
                                conf_threshold=1e9),
                   data, RuntimeConfig(rounds=1))
    _, reports = gated.run(jax.random.PRNGKey(0))
    assert reports[0].upload_bytes == 0
    assert reports[0].download_bytes_per_client == 0


def test_federation_run_rounds_follow_fed_cfg():
    """fed_cfg.rounds is authoritative even when a runtime_cfg is passed
    for scenario knobs (its default rounds must not leak in)."""
    data = _data()
    fed = federation.FedConfig(n_clients=8, rounds=1, local_epochs=1)
    _, hist = federation.run(data, TM_CFG, fed, jax.random.PRNGKey(0),
                             runtime_cfg=RuntimeConfig(
                                 codec=CodecConfig("int8")))
    assert len(hist) == 1


def test_weighted_clustered_mean_matches_unweighted_at_one():
    key = jax.random.PRNGKey(0)
    vals = jax.random.normal(key, (12, 7))
    assign = jax.random.randint(key, (12,), 0, 4)
    a = masked_collectives.clustered_mean(vals, assign, 4)
    b = masked_collectives.clustered_weighted_mean(
        vals, assign, jnp.ones(12), 4)
    assert jnp.allclose(a, b, atol=1e-5)


def test_engine_runs_dl_baseline_strategies():
    data = _data(n_clients=4)
    for strat in (FedAvgStrategy(n_features=100, n_classes=10, n_hidden=16,
                                 local_epochs=1),
                  IFCAStrategy(n_features=100, n_classes=10, n_hidden=16,
                               k=3, local_epochs=1)):
        eng = Engine(strat, data, RuntimeConfig(rounds=2))
        _, reports = eng.run(jax.random.PRNGKey(0))
        assert 0.0 <= float(reports[-1].mean_accuracy) <= 1.0
        assert reports[-1].upload_bytes > 0
        # FedAvg slots all 0; IFCA slots within [0, k)
        assert int(reports[-1].assignment.max()) < strat.n_slots
