"""Distributed TPFL: one federated round as a single pjit program, plus
the CLI front-end of the federated runtime.

Clients are a stacked `TMParams` pytree sharded over the mesh's FSDP
("data"/"pod") axes — each shard trains its slice of the client
population locally; the confidence-clustered aggregation lowers to the
masked collective of `repro.fl.masked_collectives`.  A FedAvg-on-TM
round (full-state tree mean, no clustering) is provided as the
communication baseline: the collective-bytes delta between the two
lowered programs is the paper's Table-4/5 claim, measured in the HLO
(EXPERIMENTS.md §Perf).

CLI — run any federation scenario through `repro.fl.runtime`:

  PYTHONPATH=src python -m repro.launch.fed_train \\
      --participation 0.1 --dropout 0.2 --codec int8

reports per-round mean accuracy plus byte-exact upload/download totals
(metered from the actual encoded wire buffers).  Default knobs (full
participation, sync, float32) reproduce the legacy ``federation.run``
metrics exactly.  ``--strategy`` selects any Table-5 method — including
``flis_dc`` / ``flis_hc`` (dynamic server-side clustering, capped at
``--max-slots`` rows, probe set of ``--probe-size`` samples) and
``fedtm`` — see ``docs/baselines.md``.  ``--mesh clients:8`` runs the same round shard-mapped
over an 8-device ``clients`` mesh axis (bit-identical to in-process —
the conformance suite pins it; spawn virtual CPU devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``).  ``--mode
async`` composes with ``--mesh``: the upload buffer is device state and
the buffered round runs shard-mapped end-to-end (``--async-buffer
host`` keeps the in-process numpy reference).  See
``docs/async-runtime.md``.

Telemetry: ``--telemetry-dir RUN_DIR`` records the run through the
observability plane (``repro.fl.obs``) — a manifest (config, seed,
mesh, git sha, jax version) plus one structured JSONL event per round
(accuracy deciles, cluster churn/occupancy, staleness histograms, wire
bytes, per-phase wall times) — rendered afterwards by ``python -m
repro.fl.obs summarize RUN_DIR``.  ``--profile-dir`` additionally
captures a ``jax.profiler`` device trace.  Instrumentation never
perturbs the round: obs-on == obs-off bit for bit, pinned by the
conformance suite.  Round output always includes the worst-decile
client accuracy (the distributional pFL metric), telemetry or not.
See ``docs/observability.md``.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import clustering, federation, tm
from repro.data.partition import ClientData


def make_tpfl_round(tm_cfg: tm.TMConfig,
                    fed_cfg: federation.FedConfig) -> Callable:
    """(client_params, cluster_weights, data, key) → (params, cw, metrics).

    Pure-array in/out (jit/pjit-able; all Python ints stay abstract)."""

    def round_fn(client_params: tm.TMParams, cluster_weights: jnp.ndarray,
                 data: ClientData, key: jax.Array):
        state = federation.TPFLState(client_params, cluster_weights)
        params, c_top, uploads = federation._phase_a(
            state, data, key, tm_cfg, fed_cfg)
        res = clustering.aggregate(
            uploads.reshape(-1, tm_cfg.n_clauses), c_top.reshape(-1),
            tm_cfg.n_classes, prev=cluster_weights)
        params = federation._phase_d(params, c_top, res.cluster_weights)
        acc = jax.vmap(lambda p, x, y: tm.accuracy(p, x, y, tm_cfg))(
            params, data.x_test, data.y_test)
        return params, res.cluster_weights, {
            "mean_accuracy": acc.mean(),
            "assignment": res.assignment,
            "cluster_counts": res.counts,
        }

    return round_fn


def make_fedavg_tm_round(tm_cfg: tm.TMConfig,
                         fed_cfg: federation.FedConfig) -> Callable:
    """FedAvg over the *full* TM state (TA states + all class weights) —
    the no-personalization baseline whose all-reduce moves C·m·(2o+1)
    numbers per client instead of TPFL's m."""

    def round_fn(client_params: tm.TMParams, data: ClientData,
                 key: jax.Array):
        keys = jax.random.split(key, fed_cfg.n_clients)
        params = jax.vmap(lambda p, xt, yt, k: tm.train(
            p, xt, yt, k, tm_cfg, epochs=fed_cfg.local_epochs))(
            client_params, data.x_train, data.y_train, keys)
        # full-model averaging — the global all-reduce TPFL avoids
        ta_mean = jnp.round(params.ta_state.astype(jnp.float32).mean(0)
                            ).astype(jnp.int32)
        w_mean = jnp.round(params.weights.astype(jnp.float32).mean(0)
                           ).astype(jnp.int32)
        n = params.ta_state.shape[0]
        params = tm.TMParams(
            ta_state=jnp.broadcast_to(ta_mean, params.ta_state.shape),
            weights=jnp.broadcast_to(w_mean, params.weights.shape))
        acc = jax.vmap(lambda p, x, y: tm.accuracy(p, x, y, tm_cfg))(
            params, data.x_test, data.y_test)
        return params, {"mean_accuracy": acc.mean()}

    return round_fn


def abstract_fed_inputs(tm_cfg: tm.TMConfig, fed_cfg: federation.FedConfig,
                        mesh, n_train: int = 64, n_test: int = 32,
                        n_conf: int = 32):
    """ShapeDtypeStructs for a mesh-wide federated round (dry-run)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.sharding import rules

    n = fed_cfg.n_clients
    o = tm_cfg.n_features
    b = rules._fsdp_or_none(mesh, n)

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    C, m, L = tm_cfg.n_classes, tm_cfg.n_clauses, tm_cfg.n_literals
    params = tm.TMParams(
        ta_state=sds((n, C, m, L), jnp.int32, P(b, None, None, None)),
        weights=sds((n, C, m), jnp.int32, P(b, None, None)))
    cw = sds((C, m), jnp.float32, P(None, None))

    def dat(k, dt=jnp.uint8):
        return sds((n, k, o) if dt == jnp.uint8 else (n, k), dt,
                   P(b, None, None) if dt == jnp.uint8 else P(b, None))

    data = ClientData(
        x_train=dat(n_train), y_train=dat(n_train, jnp.int32),
        x_test=dat(n_test), y_test=dat(n_test, jnp.int32),
        x_conf=dat(n_conf), y_conf=dat(n_conf, jnp.int32),
        mixtures=sds((n, C), jnp.float32, P(b, None)))
    key = sds((2,), jnp.uint32, P(None))
    return params, cw, data, key


def abstract_round_inputs(tm_cfg: tm.TMConfig, fed_cfg: federation.FedConfig,
                          mesh, **data_kw):
    """ShapeDtypeStructs for the engine's shard-mapped sync round
    (:func:`repro.fl.runtime.executors.build_sharded_round`): the
    :func:`abstract_fed_inputs` set (single round key included, for the
    legacy-builder baselines) plus per-client rng keys and the arrival
    mask, and the client axis name the round collectives run over.
    The server matrix is wrapped in the v2 strategy-owned
    :class:`~repro.fl.runtime.strategy.ServerState` pytree (TPFL
    carries no aux) — what the dry-run lowers on the production mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.fl.runtime.strategy import ServerState
    from repro.sharding import rules

    params, cw, data, key = abstract_fed_inputs(tm_cfg, fed_cfg, mesh,
                                                **data_kw)
    n = fed_cfg.n_clients
    b = rules._fsdp_or_none(mesh, n)

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    keys = sds((n, 2), jnp.uint32, P(b, None))
    arrive = sds((n,), jnp.bool_, P(b))
    return params, ServerState(cw), data, key, keys, arrive, b


def abstract_async_inputs(tm_cfg: tm.TMConfig, fed_cfg: federation.FedConfig,
                          mesh, capacity: int = 512, j_slots: int = 1):
    """ShapeDtypeStructs for the engine's shard-mapped *async* buffered
    update (:func:`repro.fl.runtime.executors.build_sharded_async_update`):
    one round's upload lanes (``n_clients · j_slots`` rows — pass the
    strategy's ``j_slots`` so multi-cluster sharing sizes them right)
    sharded over the mesh's FSDP axes, the fixed-capacity device-buffer
    lanes + server replicated.  What the dry-run lowers to price the
    async round's collectives."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.sharding import rules

    n = fed_cfg.n_clients * j_slots
    b = rules._fsdp_or_none(mesh, n)

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    C, m = tm_cfg.n_classes, tm_cfg.n_clauses
    up = (sds((n, m), jnp.float32, P(b, None)),   # payload vectors
          sds((n,), jnp.int32, P(b)),             # slot ids
          sds((n,), jnp.int32, P(b)),             # maturity rounds
          sds((n,), jnp.float32, P(b)),           # staleness weights
          sds((n,), jnp.bool_, P(b)))             # validity
    buf = (sds((capacity, m), jnp.float32, P(None, None)),
           sds((capacity,), jnp.int32, P(None)),
           sds((capacity,), jnp.int32, P(None)),
           sds((capacity,), jnp.float32, P(None)),
           sds((capacity,), jnp.bool_, P(None)),
           sds((capacity,), jnp.int32, P(None)))
    round_idx = sds((), jnp.int32, P())
    prev = sds((C, m), jnp.float32, P(None, None))
    return buf, up, round_idx, prev, b


# ---------------------------------------------------------------------------
# CLI: scenario runner on the federated runtime
# ---------------------------------------------------------------------------

STRATEGY_CHOICES = ("tpfl", "fedavg", "fedprox", "ifca", "flis_dc",
                    "flis_hc", "fedtm")


def _build_strategy(name: str, tm_cfg: tm.TMConfig,
                    fed_cfg: federation.FedConfig, pool,
                    max_slots: int = 8, probe_size: int = 64):
    """``pool`` is anything with ``n_features`` / ``n_classes`` (an
    ingest :class:`~repro.data.ingest.registry.Pool`).  The TM-based
    strategies (TPFL, FedTM) take the TM config; the MLP baselines size
    themselves from the pool."""
    from repro.fl.runtime.strategy import (FedTMStrategy,
                                           build_baseline_strategy)
    if name == "tpfl":
        return federation._strategy(tm_cfg, fed_cfg)
    if name == "fedtm":
        return FedTMStrategy(tm_cfg, local_epochs=fed_cfg.local_epochs)
    return build_baseline_strategy(
        name, n_features=pool.n_features, n_classes=pool.n_classes,
        local_epochs=fed_cfg.local_epochs, max_slots=max_slots,
        probe_size=probe_size)


def build_scenario(*, dataset: str, data_dir: str | None = None,
                   encoding: str = "bool", clients: int = 20,
                   clauses: int = 48, seed: int = 0, experiment: int = 5,
                   writers: int | None = None, rounds: int = 5,
                   local_epochs: int = 2, strategy: str = "tpfl",
                   max_slots: int = 8, probe_size: int = 64):
    """One materialized federation scenario: (pool, partitioned client
    data, TM config, fed config, strategy).

    Shared by the train and serve drivers so a serving process
    reconstructs exactly the training run's setup from the same knobs —
    same dataset/seed → the same partition and the same per-client init
    chain, same strategy template → the same engine-state structure a
    published checkpoint must decode into."""
    from repro.data.ingest import natural, registry as datasets

    pool = datasets.load(dataset, data_dir=data_dir, encoding=encoding,
                         n_samples=6000, side=12, seed=seed,
                         n_writers=writers or max(25, clients))
    # writer-tagged pools take the natural writer-identity split
    # (the real per-writer ``sizes`` drive --sampling weighted),
    # the rest the paper's Dirichlet split
    data = natural.partition_pool(
        pool, n_clients=clients, n_train=80, n_test=40, n_conf=40,
        key=jax.random.PRNGKey(seed + 1), experiment=experiment)
    tm_cfg = tm.TMConfig(n_classes=pool.n_classes, n_clauses=clauses,
                         n_features=pool.n_features, n_states=63,
                         s=5.0, T=40)
    fed_cfg = federation.FedConfig(n_clients=clients, rounds=rounds,
                                   local_epochs=local_epochs)
    strat = _build_strategy(strategy, tm_cfg, fed_cfg, pool,
                            max_slots=max_slots, probe_size=probe_size)
    return pool, data, tm_cfg, fed_cfg, strat


def main(argv: list[str] | None = None) -> dict:
    import argparse

    from repro.data.ingest import registry as datasets
    from repro.fl.runtime import (CodecConfig, Engine, RuntimeConfig,
                                  SchedulerConfig, checkpointing)
    from repro.launch import compile_cache

    compile_cache.configure()

    ap = argparse.ArgumentParser(
        description="Federated runtime scenario runner")
    ap.add_argument("--strategy", default="tpfl",
                    choices=STRATEGY_CHOICES)
    ap.add_argument("--max-slots", type=int, default=8,
                    help="FLIS: server slot rows — dynamic clusters are "
                         "recomputed each round and capped at this many")
    ap.add_argument("--probe-size", type=int, default=64,
                    help="FLIS: size of the server-side probe set drawn "
                         "from the confidence split")
    ap.add_argument("--dataset", default="synthmnist",
                    choices=datasets.names())
    ap.add_argument("--data-dir", default=None,
                    help="dataset cache (IDX/LEAF files; the offline "
                         "mirror populates it, real files are used "
                         "transparently — see docs/datasets.md).  "
                         "Required for the real flavours; synth* fall "
                         "back to in-memory generation without it")
    ap.add_argument("--encoding", default="bool", metavar="SPEC",
                    help="feature encoding: bool[:threshold] | "
                         "thermometer[:levels] | quantile[:levels]")
    ap.add_argument("--experiment", type=int, default=5,
                    help="paper setup 1..5 (fraction of non-IID clients)")
    ap.add_argument("--writers", type=int, default=None,
                    help="LEAF mirror size (writers ≥ clients; default "
                         "max(25, clients)).  Only shapes a cache being "
                         "written — existing shards win; clear the "
                         "data dir to regenerate")
    ap.add_argument("--clients", type=int, default=20)
    ap.add_argument("--n-clients", type=int, default=None,
                    dest="n_clients", metavar="N",
                    help="simulated-scale population: stream per-writer "
                         "LEAF shards on demand for the sampled cohort "
                         "instead of materializing the pool (clients "
                         "map cyclically onto writers beyond the writer "
                         "count).  Scales past RAM; requires --data-dir "
                         "and --client-store mmap.  Overrides --clients")
    ap.add_argument("--active", type=int, default=None, metavar="K",
                    help="sample K clients per round (sets "
                         "--participation K/N; the engine's working set "
                         "is O(K))")
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--local-epochs", type=int, default=2)
    ap.add_argument("--clauses", type=int, default=48)
    ap.add_argument("--seed", type=int, default=0)
    # scheduler knobs
    ap.add_argument("--participation", type=float, default=1.0)
    ap.add_argument("--sampling", default="uniform",
                    choices=("uniform", "weighted", "round_robin"))
    ap.add_argument("--dropout", type=float, default=0.0)
    ap.add_argument("--straggler", type=float, default=0.0)
    ap.add_argument("--max-staleness", type=int, default=2)
    # wire codec
    ap.add_argument("--codec", default="float32",
                    choices=("float32", "int8", "int4"))
    ap.add_argument("--sparse", action="store_true",
                    help="sparse delta encoding of uploads")
    ap.add_argument("--error-feedback", action="store_true",
                    dest="error_feedback",
                    help="compression v2: per-client error-feedback "
                         "residual memory on the lossy int8/int4 uplink "
                         "— each frame's quantization error is added "
                         "back before the next encode, so the bias "
                         "cancels over rounds (carried in the engine "
                         "state, checkpoint-resumable)")
    ap.add_argument("--index-coding", default="u2", dest="index_coding",
                    choices=("u2", "vrle"),
                    help="compression v2: sparse-delta index stream "
                         "coding — u2 = raw uint16 indices, vrle = "
                         "varint-coded gap/run-length (smaller for "
                         "clustered or dense masks; requires --sparse)")
    # real transport (docs/transport.md)
    ap.add_argument("--transport", default="inprocess",
                    choices=("inprocess", "loopback", "socket"),
                    help="where the federated round's client half runs: "
                         "inprocess = the single-process engine, "
                         "loopback = worker peers behind in-memory "
                         "framed queues (bit-identical to inprocess on "
                         "the identity wire, conformance-pinned), "
                         "socket = real worker subprocesses over local "
                         "TCP, exchanging the encoded uplink/downlink "
                         "frames as length-prefixed messages")
    ap.add_argument("--workers", type=int, default=0, metavar="M",
                    help="transport worker peers; the client population "
                         "is partitioned into M contiguous blocks "
                         "(required ≥ 1 for --transport loopback/socket)")
    # aggregation mode
    ap.add_argument("--mode", default="sync", choices=("sync", "async"))
    ap.add_argument("--async-min-uploads", type=int, default=4)
    ap.add_argument("--buffer-capacity", type=int, default=64)
    ap.add_argument("--staleness-discount", type=float, default=0.5)
    ap.add_argument("--async-buffer", default="device",
                    choices=("device", "host"),
                    help="async upload buffer: device = one compiled "
                         "masked update per round (works with --mesh), "
                         "host = the numpy reference loop")
    # execution backend
    ap.add_argument("--backend", default=None,
                    choices=("inprocess", "shardmap"),
                    help="round executor; 'shardmap' without --mesh uses "
                         "a clients mesh of all visible devices "
                         "(equivalent to --mesh clients)")
    ap.add_argument("--mesh", default=None, metavar="clients[:N]",
                    help="run the round shard-mapped over a clients mesh "
                         "axis of N devices (default: all visible); "
                         "composes with --mode async (device buffer)")
    ap.add_argument("--collective", default="gather",
                    choices=("gather", "psum"),
                    help="mesh aggregation lowering: gather is bit-exact "
                         "with in-process, psum is C*m collective bytes")
    ap.add_argument("--tm-backend", default="ref",
                    choices=("ref", "pallas"),
                    help="TM compute path for tpfl/fedtm: ref = pure-jnp "
                         "reference, pallas = fused TM kernels (one "
                         "client-batched launch per round stage; "
                         "interpret mode on CPU, Mosaic on TPU).  "
                         "Bit-identical outputs, conformance-pinned; "
                         "no-op for the MLP baselines")
    # host-side client store (docs/client-store.md)
    ap.add_argument("--client-store", default="resident",
                    dest="client_store", choices=("resident", "mmap"),
                    help="mmap keeps client rows in a memory-mapped "
                         "host store and gathers/spills only the K "
                         "sampled rows per round — device/RAM O(K), "
                         "bit-identical to resident (conformance-"
                         "pinned)")
    ap.add_argument("--store-dir", default=None, metavar="DIR",
                    help="client-store root (default: fresh temp dir); "
                         "reuse it together with --ckpt-dir to resume")
    ap.add_argument("--store-eval", default="full", dest="store_eval",
                    choices=("full", "sampled"),
                    help="mmap evaluation scope: full = chunked "
                         "population eval (resident-identical reports), "
                         "sampled = the K merged clients only (the "
                         "simulated-scale setting)")
    # checkpointing
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    # telemetry (repro.fl.obs — docs/observability.md)
    ap.add_argument("--telemetry-dir", default=None, metavar="RUN_DIR",
                    help="record the run: manifest.json + per-round "
                         "events.jsonl (accuracy deciles, cluster "
                         "churn, staleness, wire bytes, phase wall "
                         "times); render with `python -m repro.fl.obs "
                         "summarize RUN_DIR`.  Never perturbs the "
                         "round (obs-on == obs-off, conformance-pinned)")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="additionally capture a jax.profiler device "
                         "trace (TensorBoard-loadable) for the run")
    args = ap.parse_args(argv)

    key = jax.random.PRNGKey(args.seed)
    streaming = args.n_clients is not None
    if streaming:
        if args.client_store != "mmap":
            raise SystemExit(
                "--n-clients streams the population on demand — it "
                "requires --client-store mmap (there is no materialized "
                "pool for the resident engine to hold)")
        if args.data_dir is None:
            raise SystemExit("--n-clients needs --data-dir (LEAF shards "
                             "to stream; the mirror writes them)")
        if args.strategy in ("flis_dc", "flis_hc"):
            raise SystemExit(
                "flis_* draws its server probe set from materialized "
                "client data at init — not available on a streamed "
                "population; use --clients instead of --n-clients")
        pool = datasets.load_stream(
            args.dataset, args.data_dir, encoding=args.encoding,
            n_samples=6000, side=12, seed=args.seed,
            n_writers=args.writers or 25)
        from repro.fl.store import StreamingClientData
        data = StreamingClientData(
            pool, n_clients=args.n_clients, n_train=80, n_test=40,
            n_conf=40, key=jax.random.PRNGKey(args.seed + 1))
        n_clients = args.n_clients
        tm_cfg = tm.TMConfig(
            n_classes=pool.n_classes, n_clauses=args.clauses,
            n_features=pool.n_features, n_states=63, s=5.0, T=40)
        fed_cfg = federation.FedConfig(n_clients=n_clients,
                                       rounds=args.rounds,
                                       local_epochs=args.local_epochs)
        strategy = _build_strategy(args.strategy, tm_cfg, fed_cfg, pool,
                                   max_slots=args.max_slots,
                                   probe_size=args.probe_size)
    else:
        pool, data, tm_cfg, fed_cfg, strategy = build_scenario(
            dataset=args.dataset, data_dir=args.data_dir,
            encoding=args.encoding, clients=args.clients,
            clauses=args.clauses, seed=args.seed,
            experiment=args.experiment, writers=args.writers,
            rounds=args.rounds, local_epochs=args.local_epochs,
            strategy=args.strategy, max_slots=args.max_slots,
            probe_size=args.probe_size)
        n_clients = args.clients

    participation = args.participation
    if args.active is not None:
        if not 0 < args.active <= n_clients:
            raise SystemExit(f"--active must be in [1, {n_clients}]")
        participation = args.active / n_clients

    mesh = None
    if args.mesh is None and args.backend == "shardmap":
        args.mesh = "clients"            # all visible devices
    if args.mesh is not None:
        if args.backend == "inprocess":
            raise SystemExit("--backend inprocess contradicts --mesh")
        from repro.launch.mesh import make_clients_mesh
        name, _, count = args.mesh.partition(":")
        if name != "clients":
            raise SystemExit(f"--mesh must be clients[:N], got {args.mesh!r}")
        mesh = make_clients_mesh(int(count) if count else None)

    rt_cfg = RuntimeConfig(
        rounds=args.rounds,
        scheduler=SchedulerConfig(
            participation=participation, sampling=args.sampling,
            dropout=args.dropout, straggler=args.straggler,
            max_staleness=args.max_staleness),
        codec=CodecConfig(args.codec, sparse=args.sparse,
                          error_feedback=args.error_feedback,
                          index_coding=args.index_coding),
        transport=args.transport, workers=args.workers,
        aggregation=args.mode,
        async_min_uploads=args.async_min_uploads,
        buffer_capacity=args.buffer_capacity,
        staleness_discount=args.staleness_discount,
        async_buffer=args.async_buffer,
        backend="shardmap" if mesh is not None else "inprocess",
        mesh_collective=args.collective,
        tm_backend=args.tm_backend,
        checkpoint_dir=args.ckpt_dir, checkpoint_every=args.ckpt_every,
        client_store=args.client_store, store_dir=args.store_dir,
        store_eval=args.store_eval)

    telemetry = None
    if args.telemetry_dir or args.profile_dir:
        from repro.fl import obs
        telemetry = obs.RunRecorder(run_dir=args.telemetry_dir,
                                    profile_dir=args.profile_dir)
    runner = None
    if args.transport != "inprocess":
        if args.resume:
            raise SystemExit("--resume is an in-process engine feature; "
                             "transport runs restart from round 0")
        if streaming:
            raise SystemExit("--transport partitions a materialized "
                             "population over worker blocks — not "
                             "available with --n-clients streaming")
        from repro.fl.transport import TransportEngine
        spec = None
        if args.transport == "socket":
            # worker subprocesses rebuild the identical scenario from
            # these knobs (build_scenario is deterministic in them)
            spec = {"scenario": dict(
                dataset=args.dataset, data_dir=args.data_dir,
                encoding=args.encoding, clients=args.clients,
                clauses=args.clauses, seed=args.seed,
                experiment=args.experiment, writers=args.writers,
                rounds=args.rounds, local_epochs=args.local_epochs,
                strategy=args.strategy, max_slots=args.max_slots,
                probe_size=args.probe_size)}
        runner = TransportEngine(strategy, data, rt_cfg,
                                 telemetry=telemetry, spec=spec)
        engine = runner.eng
    else:
        engine = Engine(strategy, data, rt_cfg, mesh=mesh,
                        telemetry=telemetry)
    if telemetry is not None:
        telemetry.start(obs.build_manifest(
            config=rt_cfg, seed=args.seed, mesh=mesh,
            extra={"strategy": args.strategy, "dataset": args.dataset,
                   "encoding": args.encoding, "n_clients": n_clients,
                   "client_store": args.client_store,
                   "rounds": args.rounds, "argv": argv,
                   "collective_payload_bytes":
                       engine.collective_payload_bytes()}))

    state, remaining = None, None
    if args.resume and args.ckpt_dir:
        latest = checkpointing.latest(args.ckpt_dir)
        if latest is not None:
            state = checkpointing.restore(
                latest, engine.init(jax.random.PRNGKey(args.seed)))
            # complete the originally requested total, don't extend it
            remaining = max(0, args.rounds - int(state.round_idx))
            print(f"resumed from {latest} "
                  f"({remaining} of {args.rounds} rounds remaining)",
                  flush=True)
            if remaining == 0:
                print("nothing to do: run already complete", flush=True)
                return {"final_accuracy": None, "acc_per_round": [],
                        "upload_bytes": 0,
                        "download_bytes_broadcast": 0,
                        "download_bytes_per_client": 0}

    if runner is not None:
        where = (f"{args.transport} transport, {args.workers} worker "
                 f"{'peers' if args.transport == 'loopback' else 'processes'}")
    elif mesh is None:
        where = "in-process"
    else:
        where = f"shard_map over {engine.executor.n_shards}-device " \
                f"clients mesh ({args.collective})"
    if streaming:
        split = f"streamed ({len(pool.users)} writers, cyclic)"
    elif getattr(pool, "writers", None) is not None:
        split = "writer-natural"
    else:
        split = f"exp{args.experiment}"
    print(f"{args.strategy} on {args.dataset} [{args.encoding}, "
          f"{pool.n_features}f] {split}: "
          f"{n_clients} clients, K={engine.scheduler.k}/round, "
          f"store={args.client_store}, dropout={args.dropout}, "
          f"codec={args.codec}"
          f"{'+sparse' if args.sparse else ''}, mode={args.mode}, "
          f"backend={where}", flush=True)
    if args.sampling == "weighted" and engine.scheduler.p is not None:
        p = engine.scheduler.p
        print(f"weighted sampling from partition sizes: "
              f"p in [{float(p.min()):.4f}, {float(p.max()):.4f}]",
              flush=True)
    try:
        if runner is not None:
            state, reports = runner.run(key)
        else:
            state, reports = engine.run(key, state=state, rounds=remaining)
    finally:
        if telemetry is not None:
            telemetry.close()

    # worst-decile / per-decile client accuracy — the distributional
    # personalization metric (ROADMAP item 5's "honest pFL metric"),
    # derived from the report's per_client_accuracy; no engine change
    from repro.fl.obs.events import accuracy_deciles, worst_decile_mean

    up = down_bc = down_pc = st_rd = st_wr = 0
    for rep in reports:
        up += rep.upload_bytes
        down_bc += rep.download_bytes_broadcast
        down_pc += rep.download_bytes_per_client
        st_rd += rep.store_read_bytes
        st_wr += rep.store_written_bytes
        extra = ""
        if args.mode == "async":
            extra = (f" agg={rep.aggregated_uploads}"
                     f" buf={rep.buffered_uploads}"
                     f" evict={rep.evicted_uploads}")
        if runner is not None:
            extra += (f" wire_tx={rep.wire_tx_bytes}B"
                      f" wire_rx={rep.wire_rx_bytes}B")
        print(f"round {rep.round_idx:3d}: "
              f"acc={float(rep.mean_accuracy):.4f} "
              f"w10%={worst_decile_mean(rep.per_client_accuracy):.4f} "
              f"up={rep.upload_bytes}B "
              f"down_bc={rep.download_bytes_broadcast}B "
              f"down_pc={rep.download_bytes_per_client}B "
              f"active={int(jnp.sum(rep.participation.active))}"
              f"/{engine.scheduler.k}{extra}", flush=True)
    print(f"totals: upload={up}B ({up/1e6:.4f}MB) "
          f"download_broadcast={down_bc}B ({down_bc/1e6:.4f}MB) "
          f"download_per_client={down_pc}B ({down_pc/1e6:.4f}MB)",
          flush=True)
    if args.client_store == "mmap":
        print(f"client store: read={st_rd}B written={st_wr}B "
              f"({engine.store.written_count()} of {engine.n} rows "
              f"materialized, {engine.store.row_nbytes}B/row)",
              flush=True)
    deciles = accuracy_deciles(reports[-1].per_client_accuracy)
    print("final per-client accuracy deciles: "
          + " ".join(f"p{10 * i}={d:.3f}" for i, d in enumerate(deciles)),
          flush=True)
    if args.telemetry_dir:
        print(f"telemetry: {args.telemetry_dir} — render with "
              f"`python -m repro.fl.obs summarize {args.telemetry_dir}`",
              flush=True)
    return {"final_accuracy": float(reports[-1].mean_accuracy),
            "acc_per_round": [float(r.mean_accuracy) for r in reports],
            "final_accuracy_deciles": deciles,
            "final_worst_decile_mean": worst_decile_mean(
                reports[-1].per_client_accuracy),
            "upload_bytes": up, "download_bytes_broadcast": down_bc,
            "download_bytes_per_client": down_pc,
            "store_read_bytes": st_rd, "store_written_bytes": st_wr,
            "widths": {"classes": tm_cfg.n_classes,
                       "clauses": tm_cfg.n_clauses,
                       "literals": tm_cfg.n_literals,
                       "clients": n_clients},
            # what two runs must share to be the same run: every
            # client's accuracy and slot assignment per round, and a
            # digest of the final engine state's bytes
            "per_client_accuracy": [
                np.asarray(r.per_client_accuracy).tolist() for r in reports],
            "assignment": [np.asarray(r.assignment).tolist()
                           for r in reports],
            "state_sha256": _state_digest(state)}


def _state_digest(state) -> str:
    """sha256 over every leaf of a pytree: dtype, shape and bytes."""
    import hashlib

    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(state):
        a = np.asarray(leaf)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


if __name__ == "__main__":
    main()
