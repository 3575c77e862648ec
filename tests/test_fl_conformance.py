"""Federation conformance suite — the permanent contract that the
shard-mapped engine == the in-process engine == the legacy
``federation.run`` loop, bit for bit, for every (strategy, codec,
participation) cell; plus the property-level contracts underneath it
(codec roundtrips and byte metering, scheduler sampling distributions).

The suite runs on whatever devices are visible.  To exercise a real
multi-device ``clients`` mesh (every shard_map boundary, padding path,
and collective actually partitioned) spawn virtual CPU devices *before*
jax initializes — this is CI's second matrix job:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python -m pytest -q tests/test_fl_conformance.py
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import baselines, federation, tm
from repro.data import partition, synthetic
from repro.fl import masked_collectives
from repro.launch.mesh import make_clients_mesh
from repro.fl.runtime import (CodecConfig, Engine, FedAvgStrategy,
                              FedTMStrategy, FLISStrategy, IFCAStrategy,
                              RuntimeConfig, Scheduler, SchedulerConfig,
                              TPFLStrategy, codec)

TM_CFG = tm.TMConfig(n_classes=10, n_clauses=20, n_features=100,
                     n_states=63, s=5.0, T=20)
N_CLIENTS = 8
ROUNDS = 2

FLIS_KW = dict(n_features=100, n_classes=10, n_hidden=16, local_epochs=1,
               max_slots=4, probe_size=16)

STRATEGIES = {
    "tpfl": lambda: TPFLStrategy(TM_CFG, local_epochs=1),
    "fedavg": lambda: FedAvgStrategy(n_features=100, n_classes=10,
                                     n_hidden=16, local_epochs=1),
    "fedprox": lambda: FedAvgStrategy(n_features=100, n_classes=10,
                                      n_hidden=16, local_epochs=1,
                                      prox_mu=0.1),
    "ifca": lambda: IFCAStrategy(n_features=100, n_classes=10, n_hidden=16,
                                 k=3, local_epochs=1),
    # server-state API v2: FLIS assigns slots *server-side* per round
    # (dynamic clustering through the assign hook), FedTM is the one-slot
    # full-weight TM strategy — both must hold the same backend parity
    "flis_dc": lambda: FLISStrategy(linkage="dc", **FLIS_KW),
    "flis_hc": lambda: FLISStrategy(linkage="hc", **FLIS_KW),
    "fedtm": lambda: FedTMStrategy(TM_CFG, local_epochs=1),
}
WIRES = {
    "float32": CodecConfig("float32"),
    "int8": CodecConfig("int8"),
    "int4_sparse": CodecConfig("int4", sparse=True),
}
PARTICIPATION = {
    "full": SchedulerConfig(),
    "partial": SchedulerConfig(participation=0.5, dropout=0.25),
}


@pytest.fixture(scope="module")
def data():
    x, y, dcfg = synthetic.make_dataset("synthmnist", 1500,
                                        jax.random.PRNGKey(0), side=10)
    return partition.partition(
        x, y, dcfg.n_classes, n_clients=N_CLIENTS, experiment=5,
        key=jax.random.PRNGKey(1), n_train=40, n_test=20, n_conf=20)


def _run(strategy, data, sched, wire, backend, collective="gather",
         rounds=ROUNDS):
    cfg = RuntimeConfig(rounds=rounds, scheduler=sched, codec=wire,
                        backend=backend, mesh_collective=collective)
    engine = Engine(strategy, data, cfg)
    return engine.run(jax.random.PRNGKey(0))


def _assert_bitwise_equal_runs(sa, ra, sb, rb):
    """Every observable of the two runs is bit-identical: reports and
    final population/server state."""
    for a, b in zip(ra, rb):
        assert float(a.mean_accuracy) == float(b.mean_accuracy)
        assert (np.asarray(a.per_client_accuracy)
                == np.asarray(b.per_client_accuracy)).all()
        assert (np.asarray(a.assignment) == np.asarray(b.assignment)).all()
        assert (np.asarray(a.cluster_counts)
                == np.asarray(b.cluster_counts)).all()
        assert a.upload_bytes == b.upload_bytes
        assert a.download_bytes_broadcast == b.download_bytes_broadcast
        assert a.download_bytes_per_client == b.download_bytes_per_client
        assert a.aggregated_uploads == b.aggregated_uploads
    # the whole strategy-owned server pytree: slot matrix + aux (FLIS's
    # probe set and membership table ride along)
    for la, lb in zip(jax.tree.leaves(sa.server),
                      jax.tree.leaves(sb.server)):
        assert (np.asarray(la) == np.asarray(lb)).all()
    for la, lb in zip(jax.tree.leaves(sa.client_state),
                      jax.tree.leaves(sb.client_state)):
        assert (np.asarray(la) == np.asarray(lb)).all()


# ---------------------------------------------------------------------------
# the bit-parity matrix: shard-mapped == in-process
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("part_name", sorted(PARTICIPATION))
@pytest.mark.parametrize("wire_name", sorted(WIRES))
@pytest.mark.parametrize("strat_name", sorted(STRATEGIES))
def test_shardmap_round_is_bit_identical_to_inprocess(
        strat_name, wire_name, part_name, data):
    sched = PARTICIPATION[part_name]
    wire = WIRES[wire_name]
    sa, ra = _run(STRATEGIES[strat_name](), data, sched, wire, "inprocess")
    sb, rb = _run(STRATEGIES[strat_name](), data, sched, wire, "shardmap")
    _assert_bitwise_equal_runs(sa, ra, sb, rb)


def test_three_way_parity_with_legacy_federation_run(data):
    """The original contract, now three-way: legacy loop == in-process
    engine == shard-mapped engine for the default TPFL configuration."""
    fed = federation.FedConfig(n_clients=N_CLIENTS, rounds=ROUNDS,
                               local_epochs=1)
    key = jax.random.PRNGKey(0)
    k_init, k_rounds = jax.random.split(key)
    st = federation.init_state(TM_CFG, fed, k_init)
    legacy = []
    for r in range(fed.rounds):
        st, m = federation.run_round(
            st, data, jax.random.fold_in(k_rounds, r), TM_CFG, fed)
        legacy.append(m)

    for backend in ("inprocess", "shardmap"):
        end, hist = federation.run(
            data, TM_CFG, fed, key,
            runtime_cfg=RuntimeConfig(backend=backend))
        for a, b in zip(legacy, hist):
            assert float(a.mean_accuracy) == float(b.mean_accuracy)
            assert (np.asarray(a.assignment)
                    == np.asarray(b.assignment)).all()
            assert (np.asarray(a.cluster_counts)
                    == np.asarray(b.cluster_counts)).all()
            assert a.upload_bytes == b.upload_bytes
            assert a.download_bytes_broadcast == b.download_bytes_broadcast
            assert a.download_bytes_per_client == b.download_bytes_per_client
        assert (np.asarray(st.client_params.weights)
                == np.asarray(end.client_params.weights)).all()
        assert (np.asarray(st.cluster_weights)
                == np.asarray(end.cluster_weights)).all()


def test_psum_collective_matches_within_float_tolerance(data):
    """The communication-optimal psum lowering reduces in shard order, so
    it is allclose- (not bit-) equal; discrete observables still match."""
    sa, ra = _run(TPFLStrategy(TM_CFG, local_epochs=1), data,
                  SchedulerConfig(), WIRES["float32"], "inprocess")
    sb, rb = _run(TPFLStrategy(TM_CFG, local_epochs=1), data,
                  SchedulerConfig(), WIRES["float32"], "shardmap",
                  collective="psum")
    for a, b in zip(ra, rb):
        assert (np.asarray(a.assignment) == np.asarray(b.assignment)).all()
        assert (np.asarray(a.cluster_counts)
                == np.asarray(b.cluster_counts)).all()
        assert a.upload_bytes == b.upload_bytes
    assert np.allclose(np.asarray(sa.server.slots),
                       np.asarray(sb.server.slots), atol=1e-4)


# ---------------------------------------------------------------------------
# tm_backend parity: fused Pallas kernels == reference jnp path
# ---------------------------------------------------------------------------

TM_PALLAS_CASES = {
    "tpfl": lambda: TPFLStrategy(TM_CFG, local_epochs=1),
    # the §7 confidence gate exercises the masked-row upload path under
    # the fused kernels too
    "tpfl_thresh": lambda: TPFLStrategy(TM_CFG, local_epochs=1,
                                        top_classes=2, conf_threshold=0.0),
    "fedtm": lambda: FedTMStrategy(TM_CFG, local_epochs=1),
}


@pytest.mark.parametrize("backend", ("inprocess", "shardmap"))
@pytest.mark.parametrize("case", sorted(TM_PALLAS_CASES))
def test_pallas_tm_backend_is_bit_identical_to_ref(case, backend, data):
    """RuntimeConfig(tm_backend="pallas") swaps the TM strategies onto
    the fused client-batched Pallas kernels (interpret mode on CPU,
    Mosaic on TPU).  Every engine observable — accuracies, assignment,
    counts, metered bytes, final client/server state — must equal the
    reference path bit for bit, on both executors."""

    def run(tb):
        cfg = RuntimeConfig(rounds=ROUNDS, backend=backend, tm_backend=tb)
        return Engine(TM_PALLAS_CASES[case](), data, cfg).run(
            jax.random.PRNGKey(0))

    sa, ra = run("ref")
    sb, rb = run("pallas")
    _assert_bitwise_equal_runs(sa, ra, sb, rb)


def test_pallas_tm_backend_refuses_bank_over_vmem_budget(data):
    """The fused epoch kernel keeps one client's TA bank in VMEM; a
    machine too wide for the budget is refused at init, never run on the
    reference path behind the caller's back."""
    import dataclasses as _dc
    wide = _dc.replace(TM_CFG, n_clauses=2000, n_features=784)
    with pytest.raises(ValueError, match="VMEM"):
        Engine(TPFLStrategy(wide, local_epochs=1), data,
               RuntimeConfig(tm_backend="pallas"))


@pytest.mark.parametrize("partitionable", [True, False],
                         ids=["partitionable", "original"])
def test_pallas_tm_backend_logs_coin_draws_once(partitionable, data,
                                                caplog):
    """An engine on the fused kernels says once, at init, that the
    epoch kernel hashes the Type-I coins itself, and under which
    threefry stream; the reference backend hashes no coins in a kernel
    and says nothing."""
    import logging
    caplog.set_level(logging.INFO, logger="repro.fl.runtime.engine")
    with jax.threefry_partitionable(partitionable):
        Engine(TPFLStrategy(TM_CFG, local_epochs=1), data,
               RuntimeConfig(tm_backend="ref"))
        assert not [r for r in caplog.records if "coin" in r.getMessage()]
        Engine(TPFLStrategy(TM_CFG, local_epochs=1), data,
               RuntimeConfig(tm_backend="pallas"))
    said = [r.getMessage() for r in caplog.records
            if "coin" in r.getMessage()]
    assert len(said) == 1
    assert "hashed in the epoch kernel" in said[0]
    stream = "partitionable" if partitionable else "original"
    assert f"({stream} stream)" in said[0]


# ---------------------------------------------------------------------------
# conf_threshold byte metering: masked uploads ship nothing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tm_backend", ("ref", "pallas"))
def test_conf_threshold_zeroes_masked_rows_and_bytes(tm_backend, data):
    """A slot masked to −1 by the confidence gate must carry a *zero*
    payload row (it used to ship class 0's weights) and must not be
    metered: upload_bytes is exactly one (4 + 4·d)-byte frame per
    surviving slot of the round's assignment."""
    import dataclasses as _dc
    cfg = TM_CFG if tm_backend == "ref" \
        else _dc.replace(TM_CFG, use_kernel=True)

    # direct client_step: an all-masking threshold zeroes every row
    strat = TPFLStrategy(cfg, local_epochs=1, top_classes=2,
                         conf_threshold=1e9)
    cs, server = strat.init(jax.random.PRNGKey(0), N_CLIENTS)
    d0 = jax.tree.map(lambda a: a[0], data)
    p0 = jax.tree.map(lambda a: a[0], cs)
    if tm_backend == "pallas":
        _, up = strat.fused_client_step(
            jax.tree.map(lambda a: a[:1], cs), server.slots,
            jax.tree.map(lambda a: a[:1], data),
            jax.random.split(jax.random.PRNGKey(1), 1))
    else:
        _, up = strat.client_step(p0, server.slots, d0,
                                  jax.random.PRNGKey(1))
    assert (np.asarray(up.slots) == -1).all()
    assert (np.asarray(up.vecs) == 0).all()

    # engine metering: a mid-range gate masks some-but-not-all slots,
    # and every metered byte maps onto a surviving assignment entry.
    # The gate compares raw confidence margins, so a fixed constant can
    # land outside the data's range — derive the threshold from a probe
    # training pass instead.  One local epoch leaves most top-2 margins
    # at 0, so the median gates nothing; the upper quartile of the
    # clients' top-2 margins masks most slots and keeps the confident.
    probe = TPFLStrategy(cfg, local_epochs=1, top_classes=2)
    trained, _ = jax.vmap(probe.client_step, in_axes=(0, None, 0, 0))(
        cs, server.slots, data,
        jax.random.split(jax.random.PRNGKey(2), N_CLIENTS))
    conf = jax.vmap(lambda p, x: tm.confidence_scores(p, x, cfg))(
        trained, data.x_conf)
    mid = float(jnp.quantile(jax.lax.top_k(conf, 2)[0].astype(jnp.float32),
                             0.75))
    strat = TPFLStrategy(cfg, local_epochs=1, top_classes=2,
                         conf_threshold=mid)
    eng = Engine(strat, data, RuntimeConfig(rounds=ROUNDS))
    _, reports = eng.run(jax.random.PRNGKey(0))
    frame = 4 + 4 * strat.vec_dim
    saw_masked = saw_shared = False
    for rep in reports:
        shared = int((np.asarray(rep.assignment) >= 0).sum())
        assert rep.upload_bytes == shared * frame
        saw_shared |= shared > 0
        saw_masked |= shared < N_CLIENTS * strat.j_slots
    assert saw_shared and saw_masked, "threshold gate never exercised"

    # the all-masking gate meters zero bytes end to end
    strat = TPFLStrategy(cfg, local_epochs=1, conf_threshold=1e9)
    _, reports = Engine(strat, data, RuntimeConfig(rounds=1)).run(
        jax.random.PRNGKey(0))
    assert reports[0].upload_bytes == 0
    assert (np.asarray(reports[0].assignment) == -1).all()


def test_sharded_weighted_mean_matches_host_form():
    """The staleness-discounted sharded mean (one psum) agrees with the
    host ``clustered_weighted_mean`` it lowers."""
    n_dev = len(jax.devices())
    mesh = make_clients_mesh(n_dev)
    from jax.sharding import PartitionSpec as P

    n, d, c = 4 * n_dev, 7, 3
    key = jax.random.PRNGKey(0)
    vals = jax.random.normal(key, (n, d))
    slots = jax.random.randint(jax.random.fold_in(key, 1), (n,), -1, c)
    stale = jax.random.randint(jax.random.fold_in(key, 2), (n,), 0, 3)
    weights = 0.5 ** stale.astype(jnp.float32)

    host = masked_collectives.clustered_weighted_mean(vals, slots, weights, c)
    means, total = jax.jit(jax.shard_map(
        lambda v, s, w: masked_collectives.clustered_weighted_mean_sharded(
            v, s, w, c, "clients"),
        mesh=mesh, in_specs=(P("clients"), P("clients"), P("clients")),
        out_specs=(P(), P()), check_vma=False))(vals, slots, weights)
    assert np.allclose(np.asarray(host), np.asarray(means), atol=1e-5)
    onehot = jax.nn.one_hot(slots, c) * weights[:, None]
    assert np.allclose(np.asarray(total), np.asarray(onehot.sum(0)),
                       atol=1e-5)


def test_fed_train_mesh_cli_checkpoint_resume_bit_identical(tmp_path):
    """`fed_train --mesh clients:D` end to end: an uninterrupted mesh run
    and a checkpoint/resume cycle produce bit-identical final metrics."""
    from repro.launch import fed_train
    base = ["--clients", "8", "--rounds", "4", "--local-epochs", "1",
            "--clauses", "16", "--mesh", f"clients:{len(jax.devices())}"]
    full = fed_train.main(base)

    ck = ["--ckpt-dir", str(tmp_path), "--ckpt-every", "2"]
    interrupted = fed_train.main(base[:3] + ["2"] + base[4:] + ck)
    resumed = fed_train.main(base + ck + ["--resume"])      # rounds 2-3
    # per-round accuracies of interrupted+resumed == the uninterrupted
    # run, float-for-float, and the resumed segment's byte totals equal
    # the uninterrupted run's second half (uniform rounds)
    assert (interrupted["acc_per_round"] + resumed["acc_per_round"]
            == full["acc_per_round"])
    assert resumed["upload_bytes"] * 2 == full["upload_bytes"]
    assert (resumed["download_bytes_per_client"] * 2
            == full["download_bytes_per_client"])


# ---------------------------------------------------------------------------
# the async bit-parity matrix: device buffer == host reference == shard-mapped
# ---------------------------------------------------------------------------

ASYNC_SCHED = SchedulerConfig(participation=0.75, dropout=0.25,
                              straggler=0.5, max_staleness=2)


def _run_async(strategy, data, backend, async_buffer="device",
               collective="gather", capacity=5, rounds=3):
    """Small capacity + stragglers: every async code path fires within
    three rounds — buffering, maturity gating, aggregation, overflow
    eviction."""
    cfg = RuntimeConfig(rounds=rounds, scheduler=ASYNC_SCHED,
                        aggregation="async", async_min_uploads=2,
                        buffer_capacity=capacity, async_buffer=async_buffer,
                        backend=backend, mesh_collective=collective)
    return Engine(strategy, data, cfg).run(jax.random.PRNGKey(0))


def _assert_async_reports_equal(ra, rb):
    for a, b in zip(ra, rb):
        assert a.aggregated_uploads == b.aggregated_uploads
        assert a.buffered_uploads == b.buffered_uploads
        assert a.evicted_uploads == b.evicted_uploads


@pytest.mark.parametrize("strat_name", ["tpfl", "ifca"])
def test_async_device_buffer_bit_identical_to_host_reference(
        strat_name, data):
    """The tentpole contract: the compiled device-buffer path (insert
    scan, masked maturity gate, weighted mean) reproduces the original
    host numpy loop bit for bit — same accuracy, assignment, byte
    totals, buffer occupancy, and final state including every buffer
    lane."""
    sa, ra = _run_async(STRATEGIES[strat_name](), data, "inprocess",
                        async_buffer="host")
    sb, rb = _run_async(STRATEGIES[strat_name](), data, "inprocess",
                        async_buffer="device")
    _assert_bitwise_equal_runs(sa, ra, sb, rb)
    _assert_async_reports_equal(ra, rb)
    assert sum(r.evicted_uploads for r in ra) > 0   # overflow exercised
    for lane in ("buf_vecs", "buf_slots", "buf_ready", "buf_weight",
                 "buf_valid", "buf_seq"):
        assert (np.asarray(getattr(sa, lane))
                == np.asarray(getattr(sb, lane))).all(), lane


@pytest.mark.parametrize("strat_name", ["tpfl", "ifca"])
def test_async_shardmap_gather_bit_identical_to_inprocess(strat_name, data):
    """backend="shardmap" + aggregation="async" (the configuration that
    used to raise): the shard-mapped buffered round — uploads gathered
    in canonical order, replicated insert replay, host-form mean —
    matches the in-process device path bit for bit."""
    sa, ra = _run_async(STRATEGIES[strat_name](), data, "inprocess")
    sb, rb = _run_async(STRATEGIES[strat_name](), data, "shardmap")
    _assert_bitwise_equal_runs(sa, ra, sb, rb)
    _assert_async_reports_equal(ra, rb)
    for lane in ("buf_vecs", "buf_slots", "buf_ready", "buf_weight",
                 "buf_valid", "buf_seq"):
        assert (np.asarray(getattr(sa, lane))
                == np.asarray(getattr(sb, lane))).all(), lane


def test_async_shardmap_psum_matches_within_float_tolerance(data):
    """The C·m psum lowering of the buffered mean
    (``buffered_weighted_mean_sharded``) reduces in shard order:
    discrete observables stay exact, the server is allclose."""
    sa, ra = _run_async(TPFLStrategy(TM_CFG, local_epochs=1), data,
                        "inprocess")
    sb, rb = _run_async(TPFLStrategy(TM_CFG, local_epochs=1), data,
                        "shardmap", collective="psum")
    _assert_async_reports_equal(ra, rb)
    for a, b in zip(ra, rb):
        assert (np.asarray(a.assignment) == np.asarray(b.assignment)).all()
        assert a.upload_bytes == b.upload_bytes
    assert np.allclose(np.asarray(sa.server.slots),
                       np.asarray(sb.server.slots), atol=1e-4)
    assert (np.asarray(sa.buf_valid) == np.asarray(sb.buf_valid)).all()


def test_buffered_weighted_mean_sharded_matches_host_form():
    """The replicated-buffer psum variant slices shard blocks out of the
    same lanes the host form reduces — means must agree allclose for
    any capacity, including one that does not divide the mesh."""
    n_dev = len(jax.devices())
    mesh = make_clients_mesh(n_dev)
    from jax.sharding import PartitionSpec as P

    cap, d, c = 4 * n_dev + 3, 6, 4          # deliberately non-divisible
    key = jax.random.PRNGKey(3)
    vals = jax.random.normal(key, (cap, d))
    slots = jax.random.randint(jax.random.fold_in(key, 1), (cap,), -1, c)
    weights = jax.random.uniform(jax.random.fold_in(key, 2), (cap,))

    host = masked_collectives.clustered_weighted_mean(vals, slots, weights, c)
    means, total = jax.jit(jax.shard_map(
        lambda v, s, w: masked_collectives.buffered_weighted_mean_sharded(
            v, s, w, c, "clients", n_dev),
        mesh=mesh, in_specs=(P(), P(), P()),
        out_specs=(P(), P()), check_vma=False))(vals, slots, weights)
    assert np.allclose(np.asarray(host), np.asarray(means), atol=1e-5)
    onehot = jax.nn.one_hot(slots, c) * weights[:, None]
    assert np.allclose(np.asarray(total), np.asarray(onehot.sum(0)),
                       atol=1e-5)


def test_fed_train_mesh_async_checkpoint_resume_bit_identical(tmp_path):
    """`fed_train --mode async --mesh clients:D` with a checkpoint cycle:
    the buffer lanes are part of the state pytree, so an interrupted
    async mesh run resumes bit-identically (pending buffered uploads
    mature in the resumed half exactly as in the uninterrupted run)."""
    from repro.launch import fed_train
    base = ["--clients", "8", "--rounds", "4", "--local-epochs", "1",
            "--clauses", "16", "--mode", "async", "--straggler", "0.5",
            "--async-min-uploads", "2", "--buffer-capacity", "5",
            "--mesh", f"clients:{len(jax.devices())}"]
    full = fed_train.main(base)

    ck = ["--ckpt-dir", str(tmp_path), "--ckpt-every", "2"]
    interrupted = fed_train.main(base[:3] + ["2"] + base[4:] + ck)
    resumed = fed_train.main(base + ck + ["--resume"])      # rounds 2-3
    assert (interrupted["acc_per_round"] + resumed["acc_per_round"]
            == full["acc_per_round"])


def test_shardmap_plus_host_buffer_is_rejected():
    """The numpy reference loop cannot run on the mesh — the config
    catches the combination instead of silently degrading."""
    with pytest.raises(ValueError, match="host-buffered"):
        RuntimeConfig(backend="shardmap", aggregation="async",
                      async_buffer="host")


# ---------------------------------------------------------------------------
# server-state API v2: engine FLIS/FedTM == core/baselines reference loops
# ---------------------------------------------------------------------------

BCFG = baselines.BaselineConfig(n_clients=N_CLIENTS, rounds=ROUNDS,
                                local_epochs=1, n_hidden=16,
                                flis_probe=16, flis_max_slots=4)


@pytest.mark.parametrize("linkage", ["dc", "hc"])
def test_engine_flis_matches_reference_loop(linkage, data):
    """The new-strategy parity contract: the engine's FLIS — clients
    train and upload, the server recomputes cluster membership per
    round through the ``assign`` hook (jit-able DC label propagation /
    HC agglomerative merges) — reproduces the straight-line host
    reference loop in ``core/baselines.py`` exactly: same per-round
    assignment, same accuracy, float for float."""
    strat = FLISStrategy(linkage=linkage, **FLIS_KW)
    _, reports = Engine(strat, data, RuntimeConfig(rounds=ROUNDS)).run(
        jax.random.PRNGKey(2))
    ref = baselines.run_flis(data, BCFG, jax.random.PRNGKey(2), 100, 10,
                             linkage=linkage)
    for r in range(ROUNDS):
        assert float(reports[r].mean_accuracy) == ref.accuracy[r]
        assert (np.asarray(reports[r].assignment)[:, 0]
                == ref.assignments[r]).all()
    # the reported cluster counts are the reference labelling's counts
    counts = np.bincount(ref.assignments[-1], minlength=4)
    assert (np.asarray(reports[-1].cluster_counts) == counts).all()


def test_engine_fedtm_matches_reference_loop(data):
    """Engine FedTM (one slot, full-weight TM averaging through the
    wire codec) == the ``core/baselines.py`` reference loop: integer
    weight sums are exact in float32, so the rounded global mean — and
    hence every accuracy — is bit-identical."""
    _, reports = Engine(FedTMStrategy(TM_CFG, local_epochs=1), data,
                        RuntimeConfig(rounds=ROUNDS)).run(
        jax.random.PRNGKey(3))
    ref = baselines.run_fedtm(data, TM_CFG, BCFG, jax.random.PRNGKey(3))
    for r in range(ROUNDS):
        assert float(reports[r].mean_accuracy) == ref.accuracy[r]


def test_flis_dynamic_assignment_is_serverside(data):
    """Clients tag uploads with the row they last applied (0 before any
    broadcast); the round report's assignment is the server-side
    clustering — proof the ids were recomputed between uplink and
    aggregation, not taken from the clients."""
    strat = FLISStrategy(linkage="dc", **FLIS_KW)
    engine = Engine(strat, data, RuntimeConfig(rounds=1))
    state = engine.init(jax.random.PRNGKey(0))
    keys = jax.random.split(jax.random.PRNGKey(1), N_CLIENTS)
    _, _, proposed = engine.executor.train(
        strat, state.client_state, state.server.slots, data, keys)
    assert (np.asarray(proposed) == 0).all()      # fresh init: no row yet
    _, rep = engine.run_round(state, jax.random.PRNGKey(1))
    assert len(set(np.asarray(rep.assignment)[:, 0].tolist())) > 1


def test_flis_prev_slot_follows_applied_assignment(data):
    """The FLIS client-state ride-along: after each round, every
    client's ``prev_slot`` is the server row it last *applied* —
    advanced to the round's assignment where one was made, kept
    otherwise — and the next round's uplink tags carry exactly those
    ids to the server."""
    strat = FLISStrategy(linkage="dc", **FLIS_KW)
    engine = Engine(strat, data, RuntimeConfig(
        rounds=3, scheduler=SchedulerConfig(participation=0.5,
                                            sampling="round_robin")))
    key = jax.random.PRNGKey(0)
    k_init, k_rounds = jax.random.split(key)
    state = engine.init(k_init)
    for r in range(3):
        prev = state
        rk = jax.random.fold_in(k_rounds, r)
        part = engine.scheduler.sample(r, rk)
        state, rep = engine.run_round(state, rk)
        # the uplink tags this round are the prev_slot lanes entering it
        idx = np.asarray(part.idx)
        keys = jax.random.split(rk, N_CLIENTS)[part.idx]
        sub_cs = jax.tree.map(lambda a: a[part.idx], prev.client_state)
        sub_data = jax.tree.map(lambda a: a[part.idx], data)
        _, _, slots = engine.executor.train(
            strat, sub_cs, engine.wire_tx_server(prev.server.slots),
            sub_data, keys)
        assert (np.asarray(slots)[:, 0]
                == np.asarray(prev.client_state.prev_slot)[idx]).all()
        # prev_slot advances to the applied assignment, else is kept
        assign = np.asarray(rep.assignment)[:, 0]
        want = np.where(assign >= 0, assign,
                        np.asarray(prev.client_state.prev_slot))
        assert (np.asarray(state.client_state.prev_slot) == want).all()


def test_flis_sparse_uplink_encodes_against_prev_slot_reference(data):
    """Byte-metering pin for the ride-along: FLIS sparse-delta uplinks
    encode against the tracked reference of the row each client last
    applied (its ``prev_slot`` tag) — replayed from scratch per round,
    the metered totals must match exactly."""
    wire = CodecConfig("int8", sparse=True)
    strat = FLISStrategy(linkage="dc", **FLIS_KW)
    engine = Engine(strat, data, RuntimeConfig(rounds=3, codec=wire))
    key = jax.random.PRNGKey(0)
    k_init, k_rounds = jax.random.split(key)
    state = engine.init(k_init)
    for r in range(3):
        prev = state
        rk = jax.random.fold_in(k_rounds, r)
        part = engine.scheduler.sample(r, rk)
        state, rep = engine.run_round(state, rk)

        idx = np.asarray(part.idx)
        keys = jax.random.split(rk, N_CLIENTS)[part.idx]
        sub_cs = jax.tree.map(lambda a: a[part.idx], prev.client_state)
        sub_data = jax.tree.map(lambda a: a[part.idx], data)
        _, vecs, slots = engine.executor.train(
            strat, sub_cs, engine.wire_tx_server(prev.server.slots),
            sub_data, keys)
        np_vecs, np_slots = np.asarray(vecs), np.asarray(slots)
        expect = 0
        for c in range(idx.shape[0]):
            s = int(np_slots[c, 0])
            ref = np.asarray(prev.ref_vecs)[int(idx[c]), s]
            expect += 4 + len(codec.encode(np_vecs[c, 0], wire, ref=ref))
        assert rep.upload_bytes == expect
    # after a synced round the reference is no longer the zero row, so
    # the tag genuinely selects a nearer reference than slot-0 zeros
    assert (np.asarray(state.ref_round) >= 0).any()


def test_flis_runs_under_async_aggregation(data):
    """Async × FLIS runs: the engine aggregates on the host buffer and
    re-runs the strategy's ``assign`` over the matured uploads when it
    folds them in, so membership is decided at aggregation time."""
    eng = Engine(FLISStrategy(**FLIS_KW), data,
                 RuntimeConfig(rounds=3, aggregation="async",
                               async_min_uploads=2))
    state, reports = eng.run(jax.random.PRNGKey(0))
    assert len(reports) == 3
    assert sum(r.aggregated_uploads for r in reports) > 0
    assert all(np.isfinite(float(r.mean_accuracy)) for r in reports)
    assert np.isfinite(np.asarray(state.server.slots)).all()


def test_stringly_downloads_typo_is_rejected(data):
    """`downloads` is a validated vocabulary now: a typo used to fall
    through silently to assigned-slot broadcast/billing."""
    bad = TPFLStrategy(TM_CFG, local_epochs=1)
    object.__setattr__(bad, "downloads", "al_slots")   # the typo
    with pytest.raises(ValueError, match="downloads"):
        Engine(bad, data, RuntimeConfig())


# ---------------------------------------------------------------------------
# empty-slot retention (Alg. 2 invariant) under the v2 server_update hook
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["inprocess", "shardmap"])
def test_empty_slot_masked_mean_keeps_prev_row_bitwise(backend):
    """Property test: the per-slot masked mean with zero contributors
    keeps the previous server row bit-for-bit, through the raw-mean +
    ``server_update`` split, on both executors.  Randomized slot
    patterns with guaranteed-empty slots (fixed seed)."""
    from repro.fl.runtime.executors import (InProcessExecutor,
                                            ShardMapExecutor)
    from repro.fl.runtime.strategy import ServerState, default_server_update

    class _Spec:
        n_slots, vec_dim, j_slots = 6, 5, 1

    executor = (InProcessExecutor() if backend == "inprocess"
                else ShardMapExecutor())
    rng = np.random.default_rng(17)
    for _ in range(10):
        k = int(rng.integers(2, 9))
        empty = set(rng.choice(6, size=int(rng.integers(1, 4)),
                               replace=False).tolist())
        pool = [s for s in range(6) if s not in empty] + [-1]
        slots = jnp.asarray(rng.choice(pool, size=(k, 1)), jnp.int32)
        dec = jnp.asarray(rng.normal(size=(k, 1, 5)), jnp.float32)
        arrive = jnp.asarray(rng.random(k) < 0.8)
        prev = jnp.asarray(rng.normal(size=(6, 5)), jnp.float32)
        agg, counts = executor.masked_mean(_Spec, dec, slots, arrive)
        server = default_server_update(ServerState(prev), agg, counts)
        np_counts = np.asarray(counts)
        for s in range(6):
            if np_counts[s] == 0:
                assert (np.asarray(server.slots[s])
                        == np.asarray(prev[s])).all(), (backend, s)
        assert set(np.asarray(
            jnp.nonzero(counts)[0]).tolist()).isdisjoint(empty)


@pytest.mark.parametrize("backend", ["inprocess", "shardmap"])
def test_flis_server_update_retains_unfed_rows(backend, data):
    """Engine-level, under the custom ``server_update`` hook: FLIS rows
    whose (dynamic) cluster received no contributors this round keep
    their previous value bit-for-bit, and the aux membership table
    matches the round's counts."""
    strat = FLISStrategy(linkage="dc", **FLIS_KW)
    engine = Engine(strat, data, RuntimeConfig(rounds=1, backend=backend))
    state = engine.init(jax.random.PRNGKey(0))
    seeded = state._replace(server=state.server._replace(
        slots=jnp.arange(4 * strat.vec_dim,
                         dtype=jnp.float32).reshape(4, -1)))
    new_state, rep = engine.run_round(seeded, jax.random.PRNGKey(1))
    counts = np.asarray(rep.cluster_counts)
    for s in range(4):
        if counts[s] == 0:
            assert (np.asarray(new_state.server.slots[s])
                    == np.asarray(seeded.server.slots[s])).all()
        else:
            assert not (np.asarray(new_state.server.slots[s])
                        == np.asarray(seeded.server.slots[s])).all()
    assert (np.asarray(new_state.server.aux.members) == counts).all()


def test_server_state_checkpoint_rides_and_drift_is_loud(tmp_path, data):
    """The strategy-owned server pytree (slots + FLIS aux) rides
    checkpoints bit-for-bit; restoring under a different server-state
    layout (other strategy / max_slots) fails loudly instead of
    silently coercing."""
    from repro.fl.runtime import checkpointing
    strat = FLISStrategy(linkage="dc", **FLIS_KW)
    engine = Engine(strat, data, RuntimeConfig(rounds=1))
    state, _ = engine.run(jax.random.PRNGKey(0))
    path = checkpointing.save(tmp_path, state)
    restored = checkpointing.restore(
        path, engine.init(jax.random.PRNGKey(0)))
    for la, lb in zip(jax.tree.leaves(state.server),
                      jax.tree.leaves(restored.server)):
        assert (np.asarray(la) == np.asarray(lb)).all()

    other = Engine(TPFLStrategy(TM_CFG, local_epochs=1), data,
                   RuntimeConfig(rounds=1))
    with pytest.raises(ValueError, match="layout"):
        checkpointing.restore(path, other.init(jax.random.PRNGKey(0)))


def test_fed_train_flis_mesh_cli_runs_end_to_end():
    """The acceptance CLI: `fed_train --strategy flis_dc --max-slots 8
    --backend shardmap` runs a real shard-mapped federation and meters
    nonzero bytes."""
    from repro.launch import fed_train
    out = fed_train.main(["--strategy", "flis_dc", "--max-slots", "8",
                          "--backend", "shardmap", "--clients", "8",
                          "--rounds", "2", "--local-epochs", "1"])
    assert len(out["acc_per_round"]) == 2
    assert out["upload_bytes"] > 0


# ---------------------------------------------------------------------------
# wire-codec property tests (randomized shapes/values, fixed seed)
# ---------------------------------------------------------------------------

def test_codec_float32_roundtrip_bit_exact_random_shapes():
    rng = np.random.default_rng(7)
    cfg = CodecConfig("float32")
    for _ in range(40):
        m = int(rng.integers(1, 512))
        vec = (rng.normal(scale=10.0 ** rng.integers(-3, 4), size=m)
               .astype(np.float32))
        buf = codec.encode(vec, cfg)
        assert len(buf) == 4 * m            # metered bytes == len(buffer)
        assert (codec.decode(buf, m, cfg) == vec).all()


@pytest.mark.parametrize("name", ["int8", "int4"])
def test_codec_quantized_error_bounded_by_half_step(name):
    rng = np.random.default_rng(11)
    cfg = CodecConfig(name)
    for _ in range(40):
        m = int(rng.integers(1, 512))
        vec = (rng.normal(scale=10.0 ** rng.integers(-2, 3), size=m)
               .astype(np.float32))
        buf = codec.encode(vec, cfg)
        expect = 4 + (m if name == "int8" else (m + 1) // 2)
        assert len(buf) == expect           # metered bytes == len(buffer)
        out = codec.decode(buf, m, cfg)
        assert np.abs(out - vec).max() <= codec.roundtrip_tolerance(vec, cfg)


@pytest.mark.parametrize("name", codec.CODECS)
def test_codec_sparse_delta_decode_encode_idempotent(name):
    """A vector that already survived the wire re-encodes to itself —
    decode∘encode is a projection (bit-exact fixed point)."""
    rng = np.random.default_rng(13)
    cfg = CodecConfig(name, sparse=True)
    for _ in range(25):
        m = int(rng.integers(1, 300))
        ref = rng.normal(scale=10.0, size=m).astype(np.float32)
        mask = rng.random(m) < 0.3
        vec = (ref + mask * rng.normal(scale=2.0, size=m)
               ).astype(np.float32)
        once = codec.decode(codec.encode(vec, cfg, ref=ref), m, cfg,
                            ref=ref)
        twice = codec.decode(codec.encode(once, cfg, ref=ref), m, cfg,
                             ref=ref)
        assert (twice == once).all()


def test_engine_metered_bytes_equal_reencoded_buffer_lengths(data):
    """The engine's upload meter is Σ (4-byte slot id + len(frame)) of
    the actual frames — recompute it from the wire-visible uploads.
    Sparse frames encode against the *per-client tracked reference*
    (all-zeros on a fresh engine: no client has ever synced)."""
    strat = TPFLStrategy(TM_CFG, local_epochs=1)
    for wire in (CodecConfig("float32"), CodecConfig("int8"),
                 CodecConfig("int8", sparse=True)):
        engine = Engine(strat, data, RuntimeConfig(rounds=1, codec=wire))
        state = engine.init(jax.random.PRNGKey(0))
        part = engine.scheduler.sample(0, jax.random.PRNGKey(1))
        keys = jax.random.split(jax.random.PRNGKey(1), N_CLIENTS)
        _, vecs, slots = engine.executor.train(
            strat, state.client_state, state.server.slots, data, keys)
        _, up_bytes, _ = engine.wire_uplink(state, vecs, slots, part)
        expect = 0
        np_vecs, np_slots = np.asarray(vecs), np.asarray(slots)
        for c in range(N_CLIENTS):
            for j in range(np_slots.shape[1]):
                s = int(np_slots[c, j])
                if s < 0:
                    continue
                ref = np.asarray(state.ref_vecs)[c, s] if wire.sparse \
                    else None
                expect += 4 + len(codec.encode(np_vecs[c, j], wire,
                                               ref=ref))
        assert up_bytes == expect


# ---------------------------------------------------------------------------
# sparse-delta per-client broadcast-reference tracking
# ---------------------------------------------------------------------------

def test_sparse_refs_track_what_each_client_received(data):
    """After one full-participation sparse round, each client's
    reference holds exactly the broadcast rows it applied (its assigned
    slot), zeros elsewhere, and ``ref_round`` records the sync."""
    strat = TPFLStrategy(TM_CFG, local_epochs=1)
    engine = Engine(strat, data, RuntimeConfig(
        rounds=1, codec=CodecConfig("float32", sparse=True)))
    state, reports = engine.run(jax.random.PRNGKey(0))
    refs = np.asarray(state.ref_vecs)
    server = np.asarray(state.server.slots)
    assign = np.asarray(reports[0].assignment)
    for c in range(N_CLIENTS):
        got = {int(s) for s in assign[c] if s >= 0}
        for s in range(strat.n_slots):
            if s in got:
                assert (refs[c, s] == server[s]).all()
            else:
                assert (refs[c, s] == 0).all()
    assert (np.asarray(state.ref_round) == 0).all()


def test_sparse_uplink_encodes_against_tracked_reference(data):
    """The metering honesty contract under partial participation: every
    round's upload bytes equal a from-scratch re-encoding against the
    references each client held *entering* the round — stale or zero
    for clients that missed recent broadcasts — and clients the
    round-robin window has not reached yet remain unsynced (``ref_round
    == −1``, zero reference)."""
    wire = CodecConfig("int8", sparse=True)
    strat = IFCAStrategy(n_features=100, n_classes=10, n_hidden=16,
                         k=3, local_epochs=1)    # server init ≠ 0: a
    # tracked zero reference is distinguishable from the server row
    engine = Engine(strat, data, RuntimeConfig(
        rounds=2, codec=wire,
        scheduler=SchedulerConfig(participation=0.5,
                                  sampling="round_robin")))
    key = jax.random.PRNGKey(0)
    k_init, k_rounds = jax.random.split(key)
    state = engine.init(k_init)
    for r in range(2):
        prev = state
        rk = jax.random.fold_in(k_rounds, r)
        part = engine.scheduler.sample(r, rk)
        state, rep = engine.run_round(state, rk)

        # replay the round's wire from prev.ref_vecs, independently
        idx = np.asarray(part.idx)
        keys = jax.random.split(rk, N_CLIENTS)[part.idx]
        sub_cs = jax.tree.map(lambda a: a[part.idx], prev.client_state)
        sub_data = jax.tree.map(lambda a: a[part.idx], data)
        _, vecs, slots = engine.executor.train(
            strat, sub_cs, engine.wire_tx_server(prev.server.slots),
            sub_data, keys)
        np_vecs, np_slots = np.asarray(vecs), np.asarray(slots)
        expect = 0
        for c in range(idx.shape[0]):
            for j in range(np_slots.shape[1]):
                s = int(np_slots[c, j])
                if s < 0:
                    continue
                ref = np.asarray(prev.ref_vecs)[int(idx[c]), s]
                expect += 4 + len(codec.encode(np_vecs[c, j], wire,
                                               ref=ref))
        assert rep.upload_bytes == expect

        synced = np.zeros(N_CLIENTS, bool)
        for rr in range(r + 1):
            synced[np.asarray(
                engine.scheduler.sample(
                    rr, jax.random.fold_in(k_rounds, rr)).idx)] = True
        ref_round = np.asarray(state.ref_round)
        assert (ref_round[~synced] == -1).all()
        assert (np.asarray(state.ref_vecs)[~synced] == 0).all()
        assert (ref_round[synced] >= 0).all()
    # disjoint round-robin windows: everyone synced after 2 half-rounds
    assert (np.asarray(state.ref_round) >= 0).all()


def test_sparse_refs_ride_checkpoints(tmp_path, data):
    """The reference lanes are part of the state pytree: a sparse run
    checkpointed and restored resumes with bit-identical references and
    byte totals."""
    from repro.fl.runtime import checkpointing
    strat = TPFLStrategy(TM_CFG, local_epochs=1)
    cfg = RuntimeConfig(
        rounds=2, codec=CodecConfig("int8", sparse=True),
        scheduler=SchedulerConfig(participation=0.5, dropout=0.25))
    key = jax.random.PRNGKey(0)

    full_state, full_reports = Engine(strat, data, cfg).run(key)

    engine = Engine(strat, data, cfg)
    half, _ = engine.run(key, rounds=1)
    path = checkpointing.save(tmp_path, half)
    restored = checkpointing.restore(path, engine.init(jax.random.PRNGKey(0)))
    assert (np.asarray(restored.ref_vecs)
            == np.asarray(half.ref_vecs)).all()
    assert (np.asarray(restored.ref_round)
            == np.asarray(half.ref_round)).all()
    resumed, resumed_reports = engine.run(key, state=restored, rounds=1)
    assert resumed_reports[0].upload_bytes == full_reports[1].upload_bytes
    assert (np.asarray(resumed.ref_vecs)
            == np.asarray(full_state.ref_vecs)).all()
    assert (np.asarray(resumed.ref_round)
            == np.asarray(full_state.ref_round)).all()


# ---------------------------------------------------------------------------
# scheduler distribution tests
# ---------------------------------------------------------------------------

def _chi_square(counts: np.ndarray, expected: np.ndarray) -> float:
    return float(((counts - expected) ** 2 / expected).sum())


def test_uniform_sampling_frequencies_match_expectation():
    n, rounds = 16, 300
    s = Scheduler(SchedulerConfig(participation=0.25), n_clients=n)
    counts = np.zeros(n)
    for r in range(rounds):
        counts[np.asarray(s.sample(r, jax.random.PRNGKey(r)).idx)] += 1
    expected = np.full(n, rounds * s.k / n)
    # df = 15; the 99.99% quantile is ≈ 44 — generous but not vacuous
    assert _chi_square(counts, expected) < 60.0


def test_weighted_sampling_driven_by_partition_sizes(data):
    """The fix under test: weighted sampling uses the real per-client
    dataset sizes recorded by ``partition`` (previously plumbed through
    ``Engine(client_weights=...)`` but never connected)."""
    assert data.sizes is not None and int(data.sizes.min()) >= 1
    assert len(set(np.asarray(data.sizes).tolist())) > 1  # heterogeneous

    engine = Engine(
        TPFLStrategy(TM_CFG, local_epochs=1), data,
        RuntimeConfig(scheduler=SchedulerConfig(
            participation=1 / N_CLIENTS, sampling="weighted")))
    sizes = np.asarray(data.sizes, np.float64)
    assert np.allclose(np.asarray(engine.scheduler.p), sizes / sizes.sum(),
                       atol=1e-6)

    rounds = 600
    counts = np.zeros(N_CLIENTS)
    for r in range(rounds):
        part = engine.scheduler.sample(r, jax.random.PRNGKey(1000 + r))
        counts[np.asarray(part.idx)] += 1    # K = 1 → frequencies ∝ p
    expected = rounds * sizes / sizes.sum()
    assert _chi_square(counts, np.maximum(expected, 1.0)) < 50.0


def test_round_robin_covers_population_in_ceil_n_over_k_rounds():
    for n, k_frac in ((8, 0.5), (10, 0.4), (12, 0.25)):
        cfg = SchedulerConfig(participation=k_frac, sampling="round_robin")
        s = Scheduler(cfg, n_clients=n)
        need = -(-n // s.k)                  # ⌈N/K⌉
        seen = set()
        for r in range(need):
            seen.update(np.asarray(
                s.sample(r, jax.random.PRNGKey(r)).idx).tolist())
        assert seen == set(range(n))


def test_staleness_never_exceeds_max_staleness():
    s = Scheduler(SchedulerConfig(straggler=0.7, max_staleness=3),
                  n_clients=32)
    for r in range(50):
        st = np.asarray(s.sample(r, jax.random.PRNGKey(r)).staleness)
        assert ((st >= 0) & (st <= 3)).all()


def test_async_buffer_never_holds_an_upload_older_than_max_staleness(data):
    """Engine-level: every buffered upload matures within max_staleness
    rounds of the round that sent it."""
    max_staleness = 2
    engine = Engine(
        TPFLStrategy(TM_CFG, local_epochs=1), data,
        RuntimeConfig(rounds=3, aggregation="async",
                      async_min_uploads=10 ** 6,
                      scheduler=SchedulerConfig(straggler=1.0,
                                                max_staleness=max_staleness)))
    state = engine.init(jax.random.PRNGKey(0))
    for r in range(3):
        state, _ = engine.run_round(state, jax.random.PRNGKey(r))
        ready = np.asarray(state.buf_ready)[np.asarray(state.buf_valid)]
        assert (ready <= r + max_staleness).all()


# ---------------------------------------------------------------------------
# telemetry neutrality: obs-on == obs-off, bit for bit
# ---------------------------------------------------------------------------

def _assert_telemetry_neutral(backend, aggregation, data, tmp_path,
                              fence):
    from repro.fl.obs import RunRecorder, build_manifest, read_events

    cfg = RuntimeConfig(
        rounds=3, aggregation=aggregation, async_min_uploads=2,
        backend=backend,
        scheduler=SchedulerConfig(participation=0.75, dropout=0.25,
                                  straggler=0.5, max_staleness=2))
    s_off, r_off = Engine(TPFLStrategy(TM_CFG, local_epochs=1),
                          data, cfg).run(jax.random.PRNGKey(0))

    run_dir = tmp_path / f"{backend}-{aggregation}"
    rec = RunRecorder(run_dir=run_dir, fence=fence)
    rec.start(build_manifest(config=cfg, seed=0))
    try:
        s_on, r_on = Engine(TPFLStrategy(TM_CFG, local_epochs=1),
                            data, cfg, telemetry=rec
                            ).run(jax.random.PRNGKey(0))
    finally:
        rec.close()

    _assert_bitwise_equal_runs(s_off, r_off, s_on, r_on)
    # ...and the instrumented run really materialized its run dir
    assert (run_dir / "manifest.json").is_file()
    events = read_events(run_dir / "events.jsonl")
    assert [e["round"] for e in events] == [0, 1, 2]
    assert all(e["phases"] for e in events)


@pytest.mark.parametrize("aggregation", ["sync", "async"])
@pytest.mark.parametrize("backend", ["inprocess", "shardmap"])
def test_telemetry_is_bit_neutral(backend, aggregation, data, tmp_path):
    """The obs plane only reads: a fully instrumented run (RunRecorder
    writing a run dir, spans + fences live) produces bit-identical
    RoundReports and final state to the un-instrumented run, on both
    backends and both aggregation modes."""
    _assert_telemetry_neutral(backend, aggregation, data, tmp_path,
                              fence=True)


@pytest.mark.parametrize("aggregation", ["sync", "async"])
@pytest.mark.parametrize("backend", ["inprocess", "shardmap"])
def test_unfenced_telemetry_is_bit_neutral(backend, aggregation, data,
                                           tmp_path):
    """The same pin for the unfenced tracer a profiled run uses: spans,
    stage annotations and compile counts live, no fences."""
    _assert_telemetry_neutral(backend, aggregation, data, tmp_path,
                              fence=False)


# ---------------------------------------------------------------------------
# million-client client store: mmap engine == resident engine, bit for bit
# ---------------------------------------------------------------------------

# hook coverage on purpose: tpfl/fedtm carry the O(K) ``init_cohort``
# fast path, ifca/flis_dc take the hookless full-init fallback — both
# must hold the same parity
MMAP_STRATEGIES = ("tpfl", "ifca", "flis_dc", "fedtm")


def _run_mmap(strat_name, data, sched, wire, backend, store_dir,
              rounds=ROUNDS):
    cfg = RuntimeConfig(rounds=rounds, scheduler=sched, codec=wire,
                        backend=backend, client_store="mmap",
                        store_dir=str(store_dir))
    engine = Engine(STRATEGIES[strat_name](), data, cfg)
    state, reports = engine.run(jax.random.PRNGKey(0))
    return engine, state, reports


def _assert_mmap_run_equals_resident(sa, ra, engine_m, sm, rm):
    """Every non-store observable of the mmap run equals the resident
    run bit for bit; the population itself is compared through the
    store (the mmap state intentionally carries no O(N) lanes)."""
    for a, b in zip(ra, rm):
        assert float(a.mean_accuracy) == float(b.mean_accuracy)
        assert (np.asarray(a.per_client_accuracy)
                == np.asarray(b.per_client_accuracy)).all()
        assert (np.asarray(a.assignment) == np.asarray(b.assignment)).all()
        assert (np.asarray(a.cluster_counts)
                == np.asarray(b.cluster_counts)).all()
        assert a.upload_bytes == b.upload_bytes
        assert a.download_bytes_broadcast == b.download_bytes_broadcast
        assert a.download_bytes_per_client == b.download_bytes_per_client
        assert a.aggregated_uploads == b.aggregated_uploads
        # host-I/O gauges: the resident engine never touches a store,
        # the mmap engine spills its cohort every round
        assert a.store_read_bytes == 0 and a.store_written_bytes == 0
        assert b.store_written_bytes > 0
    for la, lb in zip(jax.tree.leaves(sa.server),
                      jax.tree.leaves(sm.server)):
        assert (np.asarray(la) == np.asarray(lb)).all()
    # O(K) contract: the mmap state holds zero-row placeholders, the
    # population lives in the store — gather it whole for comparison
    assert jax.tree.leaves(sm.client_state)[0].shape[0] == 0
    pop = engine_m.store.gather(np.arange(engine_m.n))
    for la, lb in zip(jax.tree.leaves(sa.client_state),
                      jax.tree.leaves(pop["cs"])):
        assert (np.asarray(la) == np.asarray(lb)).all()
    if "ref_vecs" in pop:       # sparse-delta reference lanes ride rows
        assert (np.asarray(sa.ref_vecs)
                == np.asarray(pop["ref_vecs"])).all()
        assert (np.asarray(sa.ref_round)
                == np.asarray(pop["ref_round"])).all()


@pytest.mark.parametrize("part_name", sorted(PARTICIPATION))
@pytest.mark.parametrize("wire_name", sorted(WIRES))
@pytest.mark.parametrize("strat_name", MMAP_STRATEGIES)
def test_mmap_store_engine_bit_identical_to_resident(
        strat_name, wire_name, part_name, data, tmp_path):
    """The tentpole contract: ``client_store="mmap"`` — K sampled rows
    gathered from the host store into the compiled round, spilled back
    after upload — reproduces the resident engine bit for bit: every
    report field, the server pytree, the full population (including
    rows the scheduler never touched, regenerated by the fault-in
    init), and the sparse-delta reference lanes now living in the
    store."""
    sched, wire = PARTICIPATION[part_name], WIRES[wire_name]
    sa, ra = _run(STRATEGIES[strat_name](), data, sched, wire, "inprocess")
    em, sm, rm = _run_mmap(strat_name, data, sched, wire, "inprocess",
                           tmp_path / "store")
    _assert_mmap_run_equals_resident(sa, ra, em, sm, rm)


@pytest.mark.parametrize("wire_name", ["float32", "int4_sparse"])
@pytest.mark.parametrize("strat_name", ["tpfl", "fedtm"])
def test_mmap_store_engine_on_shardmap_matches_resident(
        strat_name, wire_name, data, tmp_path):
    """The store sits *outside* the mesh program: a shard-mapped mmap
    run equals the in-process resident run bit for bit (gather feeds
    the same compiled round the resident engine runs)."""
    sched = PARTICIPATION["partial"]
    sa, ra = _run(STRATEGIES[strat_name](), data, sched,
                  WIRES[wire_name], "inprocess")
    em, sm, rm = _run_mmap(strat_name, data, sched, WIRES[wire_name],
                           "shardmap", tmp_path / "store")
    _assert_mmap_run_equals_resident(sa, ra, em, sm, rm)


def test_mmap_store_engine_async_matches_resident(data, tmp_path):
    """Async aggregation over the store: the device buffer lanes are
    replicated state (they ride the checkpoint, not the store), so the
    buffered mmap run must equal the resident one bit for bit —
    including every buffer lane."""
    kw = dict(rounds=3, scheduler=ASYNC_SCHED, aggregation="async",
              async_min_uploads=2, buffer_capacity=5)
    sa, ra = Engine(TPFLStrategy(TM_CFG, local_epochs=1), data,
                    RuntimeConfig(**kw)).run(jax.random.PRNGKey(0))
    em = Engine(TPFLStrategy(TM_CFG, local_epochs=1), data,
                RuntimeConfig(**kw, client_store="mmap",
                              store_dir=str(tmp_path / "store")))
    sm, rm = em.run(jax.random.PRNGKey(0))
    _assert_mmap_run_equals_resident(sa, ra, em, sm, rm)
    _assert_async_reports_equal(ra, rm)
    for lane in ("buf_vecs", "buf_slots", "buf_ready", "buf_weight",
                 "buf_valid", "buf_seq"):
        assert (np.asarray(getattr(sa, lane))
                == np.asarray(getattr(sm, lane))).all(), lane


def test_mmap_checkpoint_resume_bit_identical(tmp_path, data):
    """An interrupted mmap run (replicated-state checkpoint + flushed
    store dir) resumes bit-identically to both the uninterrupted mmap
    run and the resident engine — sparse references included, and the
    store manifest rides the checkpoint directory."""
    from repro.fl.runtime import checkpointing

    def cfg(**kw):
        return RuntimeConfig(
            rounds=2, codec=CodecConfig("int8", sparse=True),
            scheduler=SchedulerConfig(participation=0.5, dropout=0.25),
            **kw)

    key = jax.random.PRNGKey(0)
    strat = lambda: TPFLStrategy(TM_CFG, local_epochs=1)  # noqa: E731
    s_res, r_res = Engine(strat(), data, cfg()).run(key)
    em_full = Engine(strat(), data, cfg(
        client_store="mmap", store_dir=str(tmp_path / "store_full")))
    s_full, r_full = em_full.run(key)

    # interrupted half: engine-driven checkpoint at round 1 (flushes
    # the store and writes store_manifest.json alongside)
    store_b = tmp_path / "store_half"
    ck = tmp_path / "ckpt"
    e1 = Engine(strat(), data, cfg(
        client_store="mmap", store_dir=str(store_b),
        checkpoint_dir=str(ck), checkpoint_every=1))
    e1.run(key, rounds=1)
    assert (ck / checkpointing.STORE_MANIFEST_NAME).is_file()

    # resume: fresh engine over the same store dir — the `like` state
    # deliberately uses a different key (the fed_train idiom); run()
    # re-keys the store's fault-in init from the run key
    e2 = Engine(strat(), data, cfg(
        client_store="mmap", store_dir=str(store_b)))
    restored = checkpointing.restore(
        checkpointing.latest(ck), e2.init(jax.random.PRNGKey(7)))
    s_resumed, r_resumed = e2.run(key, state=restored, rounds=1)

    for rep, full_rep, res_rep in zip(r_resumed, r_full[1:], r_res[1:]):
        assert float(rep.mean_accuracy) == float(full_rep.mean_accuracy)
        assert float(rep.mean_accuracy) == float(res_rep.mean_accuracy)
        assert rep.upload_bytes == full_rep.upload_bytes == \
            res_rep.upload_bytes
    _assert_mmap_run_equals_resident(s_res, r_res[1:], e2, s_resumed,
                                     r_resumed)


def test_mmap_sampled_eval_reports_cohort_accuracy(data, tmp_path):
    """``store_eval="sampled"`` (the million-client regime: scoring all
    N every round is exactly the O(N) scan the store exists to avoid)
    reports K-shaped accuracy for the round's cohort, equal to the
    resident engine's population-shaped report sliced at the sampled
    ids."""
    sched = SchedulerConfig(participation=0.5)
    sa, ra = _run(TPFLStrategy(TM_CFG, local_epochs=1), data, sched,
                  WIRES["float32"], "inprocess")
    engine = Engine(TPFLStrategy(TM_CFG, local_epochs=1), data,
                    RuntimeConfig(rounds=ROUNDS, scheduler=sched,
                                  client_store="mmap",
                                  store_dir=str(tmp_path / "store"),
                                  store_eval="sampled"))
    key = jax.random.PRNGKey(0)
    k_init, k_rounds = jax.random.split(key)
    state = engine.init(k_init)
    for r in range(ROUNDS):
        rk = jax.random.fold_in(k_rounds, r)
        idx = np.asarray(engine.scheduler.sample(r, rk).idx)
        state, rep = engine.run_round(state, rk)
        assert np.asarray(rep.per_client_accuracy).shape == idx.shape
        assert (np.asarray(rep.per_client_accuracy)
                == np.asarray(ra[r].per_client_accuracy)[idx]).all()
        assert (np.asarray(rep.assignment)
                == np.asarray(ra[r].assignment)[idx]).all()


def test_mmap_weighted_sampling_size_table_matches_resident(data):
    """Satellite fix pin: the scheduler accepts the store's host-side
    ``int64`` size table as weights — same key, same sampled ids as the
    resident engine's device-array sizes, so resident and streamed runs
    draw identical cohorts."""
    cfg = SchedulerConfig(participation=0.25, sampling="weighted")
    dev = Scheduler(cfg, N_CLIENTS, weights=jnp.asarray(data.sizes))
    host = Scheduler(cfg, N_CLIENTS,
                     weights=np.asarray(data.sizes, np.int64))
    assert (np.asarray(dev.p) == np.asarray(host.p)).all()
    for r in range(20):
        key = jax.random.PRNGKey(100 + r)
        assert (np.asarray(dev.sample(r, key).idx)
                == np.asarray(host.sample(r, key).idx)).all()


def test_streaming_population_requires_mmap_store(data):
    """A streaming population has no resident tensors to fall back to —
    the engine rejects ``client_store="resident"`` at construction
    instead of failing deep in the first gather."""

    class _FakeStream:
        n_clients = 64
        sizes = np.full(64, 10, np.int64)

        def gather_clients(self, ids):            # pragma: no cover
            raise AssertionError("not reached")

    with pytest.raises(ValueError, match="mmap"):
        Engine(TPFLStrategy(TM_CFG, local_epochs=1), _FakeStream(),
               RuntimeConfig())
