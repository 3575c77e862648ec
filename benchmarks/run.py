"""Benchmark entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines per the harness contract,
then the per-table JSON artifacts land in benchmarks/artifacts/.

  PYTHONPATH=src python -m benchmarks.run            # all benches
  PYTHONPATH=src python -m benchmarks.run --quick    # reduced scale
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks import (ablation_multiclass, common, convergence,  # noqa: E402
                        kernel_bench, table4_tpfl, table5_comparison)

ART = Path(__file__).resolve().parent / "artifacts"


def emit_bench(dataset: str, scale, backend: str,
               data_dir: str | None = None,
               encoding: str = "bool", rounds_timed: int = 5,
               warmup_rounds: int = 1) -> dict:
    """Per-strategy sync-round wall time → BENCH_round_latency.json.

    ``warmup_rounds`` warm-up rounds (compile + jit-cache fill) then
    the **median of ≥5 timed rounds** per strategy — each round
    bracketed by ``time.perf_counter`` with an explicit
    ``jax.block_until_ready`` fence on the round's output state, so a
    timing covers the device work, not just Python dispatch.  Each
    engine runs with a telemetry :class:`~repro.fl.obs.RunRecorder`
    (in-memory, no run dir), so the artifact also records the
    **per-phase wall-time breakdown** (median per phase over the timed
    rounds) — where round time actually goes, per strategy.

    Strategies come from the CLI's one name→Strategy factory
    (``fed_train._build_strategy`` over ``fed_train.STRATEGY_CHOICES``),
    so the bench can't drift from what ``fed_train`` runs.  The two TM
    strategies (tpfl, fedtm) are additionally timed per ``tm_backend``
    (the reference jnp path and the fused Pallas kernel path — same
    round outputs bit-for-bit, conformance-pinned), so the artifact
    carries the kernel-vs-ref perf trajectory.  CI's conformance-mesh-8
    job runs this with ``--mesh`` on the 8-device clients mesh and
    uploads the JSON as an artifact, so the perf trajectory of the
    shard-mapped round has real data points.

    Artifact schema: ``rounds_timed`` / ``warmup_rounds`` (ints),
    ``round_wall_s`` ({strategy: {tm_backend: median seconds}}),
    ``phase_wall_s`` ({strategy: {tm_backend: {phase: median
    seconds}}}).  MLP strategies have a ``ref`` entry only (the TM
    backend is a no-op for them)."""
    import statistics
    import time as _time

    import jax

    from repro.core import federation
    from repro.fl.obs import RunRecorder
    from repro.fl.runtime import Engine, RuntimeConfig
    from repro.launch import fed_train

    data, pool = common.make_fed_dataset(dataset, 5, scale, 0,
                                         data_dir=data_dir,
                                         encoding=encoding)
    tm_cfg = common.bench_tm_config(dataset, pool, scale)
    n_rounds = warmup_rounds + rounds_timed
    fed_cfg = federation.FedConfig(n_clients=scale.n_clients,
                                   rounds=n_rounds,
                                   local_epochs=scale.local_epochs)
    tm_strategies = ("tpfl", "fedtm")
    out = {"dataset": dataset, "backend": backend,
           "n_devices": len(jax.devices()),
           "n_clients": scale.n_clients,
           "rounds_timed": rounds_timed,
           "warmup_rounds": warmup_rounds,
           "round_wall_s": {}, "phase_wall_s": {}}
    for name in fed_train.STRATEGY_CHOICES:
        backends = ("ref", "pallas") if name in tm_strategies else ("ref",)
        out["round_wall_s"][name] = {}
        out["phase_wall_s"][name] = {}
        for tb in backends:
            strat = fed_train._build_strategy(name, tm_cfg, fed_cfg, pool)
            rec = RunRecorder()      # in-memory: phase spans, no run dir
            engine = Engine(strat, data,
                            RuntimeConfig(rounds=n_rounds, backend=backend,
                                          tm_backend=tb),
                            telemetry=rec)
            key = jax.random.PRNGKey(0)
            k_init, k_rounds = jax.random.split(key)
            state = engine.init(k_init)
            wall = []
            for r in range(n_rounds):
                t0 = _time.perf_counter()
                state, rep = engine.run_round(
                    state, jax.random.fold_in(k_rounds, r))
                jax.block_until_ready(state)
                dt = _time.perf_counter() - t0
                rec.on_round(rep)    # pops this round's phase spans
                if r >= warmup_rounds:
                    wall.append(dt)
            out["round_wall_s"][name][tb] = round(statistics.median(wall),
                                                  4)
            timed = rec.history[warmup_rounds:]
            phases: dict[str, list[float]] = {}
            for evt in timed:
                for ph, s in (evt["phases"] or {}).items():
                    phases.setdefault(ph, []).append(s)
            out["phase_wall_s"][name][tb] = {
                ph: round(statistics.median(v), 4)
                for ph, v in sorted(phases.items())}
            print(f"bench_round_latency,"
                  f"{out['round_wall_s'][name][tb]*1e6:.0f},"
                  f"strategy={name}/{tb}", flush=True)
    ART.mkdir(exist_ok=True)
    (ART / "BENCH_round_latency.json").write_text(json.dumps(out, indent=2))
    return out


def emit_client_scale(ns=(1_000, 100_000, 1_000_000), k_active: int = 64,
                      rounds_timed: int = 2, warmup_rounds: int = 1,
                      data_dir: str | None = None) -> dict:
    """Round wall time + host-I/O bytes vs population size N →
    BENCH_client_scale.json — the O(K) working-set trajectory.

    Each point runs the mmap-store engine (``client_store="mmap"``,
    ``store_eval="sampled"``) over a streamed LEAF population of N
    simulated clients with K active per round: per-round wall time is
    ``perf_counter`` around ``run_round`` with a ``block_until_ready``
    fence (median of the timed rounds, after warm-up), and the host-I/O
    gauges come straight off the round report (``store_read_bytes`` /
    ``store_written_bytes`` — actual bytes the store read back and
    spilled).  The point of the trajectory: wall time and I/O are flat
    in N (they scale with K), while ``resident_rows`` shows how few of
    the N rows ever materialize.

    Artifact schema: ``k_active``, ``rounds_timed``, ``warmup_rounds``,
    and ``scales`` — one row per N with ``n_clients``, ``k_active``,
    ``round_wall_s``, ``store_read_bytes``, ``store_written_bytes``,
    ``store_row_bytes``, ``resident_rows``."""
    import statistics
    import tempfile
    import time as _time

    import jax

    from repro.core import tm
    from repro.data.ingest import registry as datasets
    from repro.fl.runtime import Engine, RuntimeConfig, SchedulerConfig
    from repro.fl.runtime.strategy import TPFLStrategy
    from repro.fl.store import StreamingClientData

    root = data_dir or tempfile.mkdtemp(prefix="client_scale_data_")
    spool = datasets.load_stream("synthfemnist", root, side=8,
                                 n_samples=600, seed=0, n_writers=12)
    tm_cfg = tm.TMConfig(n_classes=spool.n_classes, n_clauses=8,
                         n_features=spool.n_features, n_states=63,
                         s=5.0, T=8)
    scales = []
    for n in ns:
        n = int(n)
        k = min(k_active, n)
        sdata = StreamingClientData(spool, n_clients=n, n_train=16,
                                    n_test=8, n_conf=8,
                                    key=jax.random.PRNGKey(1))
        engine = Engine(
            TPFLStrategy(tm_cfg, local_epochs=1), sdata,
            RuntimeConfig(
                rounds=warmup_rounds + rounds_timed,
                scheduler=SchedulerConfig(participation=k / n),
                client_store="mmap",
                store_dir=tempfile.mkdtemp(prefix=f"client_store_{n}_"),
                store_eval="sampled"))
        k_init, k_rounds = jax.random.split(jax.random.PRNGKey(0))
        state = engine.init(k_init)
        wall, rd, wr = [], [], []
        for r in range(warmup_rounds + rounds_timed):
            t0 = _time.perf_counter()
            state, rep = engine.run_round(
                state, jax.random.fold_in(k_rounds, r))
            jax.block_until_ready(state)
            dt = _time.perf_counter() - t0
            if r >= warmup_rounds:
                wall.append(dt)
                rd.append(rep.store_read_bytes)
                wr.append(rep.store_written_bytes)
        scales.append({
            "n_clients": n, "k_active": engine.scheduler.k,
            "round_wall_s": round(statistics.median(wall), 4),
            "store_read_bytes": int(statistics.median(rd)),
            "store_written_bytes": int(statistics.median(wr)),
            "store_row_bytes": engine.store.row_nbytes,
            "resident_rows": engine.store.written_count()})
        print(f"bench_client_scale,"
              f"{scales[-1]['round_wall_s']*1e6:.0f},"
              f"n={n}/k={engine.scheduler.k}", flush=True)
    out = {"dataset": "synthfemnist", "k_active": k_active,
           "rounds_timed": rounds_timed, "warmup_rounds": warmup_rounds,
           "scales": scales}
    ART.mkdir(exist_ok=True)
    (ART / "BENCH_client_scale.json").write_text(json.dumps(out, indent=2))
    return out


def emit_serve_bench(dataset: str, scale, data_dir: str | None = None,
                     encoding: str = "bool",
                     batch_sizes=(1, 8, 32), requests_timed: int = 10,
                     warmup_requests: int = 3,
                     train_rounds: int = 2) -> dict:
    """Serving-plane latency → BENCH_serve_latency.json — the repo's
    second perf trajectory file.

    Trains a small TPFL population for ``train_rounds`` rounds,
    publishes the checkpoint into a fresh
    :class:`~repro.fl.serve.ModelRegistry`, then serves mixed-cluster
    batches through a :class:`~repro.fl.serve.ServingPlane` per TM
    backend (``ref`` and ``pallas`` — bit-identical predictions,
    conformance-pinned) across a batch-size sweep.  Per (backend,
    batch) cell: ``warmup_requests`` warm-up batches (compile) then
    ``requests_timed`` batches bracketed by ``perf_counter`` — the
    plane's prediction is materialized to host, so a timing covers the
    device work — reported as p50/p99 batch latency and sustained
    requests/sec.

    Artifact schema: ``batch_sizes`` (list), ``latency_s``
    ({backend: {batch: {p50, p99}}}), ``requests_per_s``
    ({backend: {batch: float}})."""
    import statistics
    import tempfile
    import time as _time

    import jax
    import numpy as np

    from repro.core import federation
    from repro.fl.runtime import Engine, RuntimeConfig, checkpointing
    from repro.fl.serve import ModelRegistry, ServingPlane
    from repro.launch import fed_train

    data, pool = common.make_fed_dataset(dataset, 5, scale, 0,
                                         data_dir=data_dir,
                                         encoding=encoding)
    tm_cfg = common.bench_tm_config(dataset, pool, scale)
    fed_cfg = federation.FedConfig(n_clients=scale.n_clients,
                                   rounds=train_rounds,
                                   local_epochs=scale.local_epochs)
    strat = fed_train._build_strategy("tpfl", tm_cfg, fed_cfg, pool)
    root = Path(tempfile.mkdtemp(prefix="serve_bench_"))
    engine = Engine(strat, data,
                    RuntimeConfig(rounds=train_rounds,
                                  checkpoint_dir=str(root / "ckpt"),
                                  checkpoint_every=train_rounds))
    engine.run(jax.random.PRNGKey(0))
    registry = ModelRegistry(root / "registry")
    registry.publish(checkpointing.latest(root / "ckpt"))

    n, n_test = scale.n_clients, scale.n_test
    x_test = np.asarray(data.x_test)
    out = {"dataset": dataset, "n_clients": n,
           "requests_timed": requests_timed,
           "warmup_requests": warmup_requests,
           "batch_sizes": list(batch_sizes),
           "latency_s": {}, "requests_per_s": {}}
    for tb in ("ref", "pallas"):
        serve_engine = Engine(strat, data, RuntimeConfig(tm_backend=tb))
        like = serve_engine.init(
            jax.random.split(jax.random.PRNGKey(0))[0])
        plane = ServingPlane(serve_engine.strategy, registry, like)
        plane.refresh()
        out["latency_s"][tb] = {}
        out["requests_per_s"][tb] = {}
        for bs in batch_sizes:
            lat = []
            for r in range(warmup_requests + requests_timed):
                ids = (np.arange(bs) * 7 + r) % n
                x = x_test[ids, (r + np.arange(bs)) % n_test]
                t0 = _time.perf_counter()
                plane.predict(ids, x)   # materializes to host (fenced)
                if r >= warmup_requests:
                    lat.append(_time.perf_counter() - t0)
            lat.sort()
            p50 = statistics.median(lat)
            p99 = lat[min(len(lat) - 1, round(0.99 * (len(lat) - 1)))]
            rps = bs * len(lat) / sum(lat)
            out["latency_s"][tb][str(bs)] = {"p50": round(p50, 6),
                                             "p99": round(p99, 6)}
            out["requests_per_s"][tb][str(bs)] = round(rps, 1)
            print(f"bench_serve_latency,{p50*1e6:.0f},"
                  f"backend={tb}/batch={bs}/rps={rps:.0f}", flush=True)
    ART.mkdir(exist_ok=True)
    (ART / "BENCH_serve_latency.json").write_text(json.dumps(out, indent=2))
    return out


def emit_wire_bench(rounds: int = 3, clients: int = 6,
                    socket_workers: int = 2) -> dict:
    """Wire-cost trajectory → BENCH_wire_bytes.json.

    Two sweeps over one small synthmnist federation:

    1. **bytes/round** per strategy × codec × compression-v2 on/off —
       the engine's codec-metered upload / download totals of the last
       round (steady state: round 0 can be cheaper while reference rows
       warm up).  v2 means error-feedback residuals on the lossy dense
       codecs and varint+RLE index coding on the sparse-delta path
       (``docs/transport.md``); float32 has no v2 variant (bit-exact,
       nothing to feed back).
    2. **socket round latency vs in-process** — the same tpfl/float32
       scenario through the in-process engine and through the real
       multi-process socket transport (``socket_workers`` worker
       subprocesses on the length-prefixed local-TCP wire), median of
       the telemetry tracer's per-round ``round`` spans (worker launch
       and jax warm-up excluded from per-round medians by taking the
       median, which discards the compile-heavy first round).

    Artifact schema: ``wire_bytes`` ({strategy: {codec_label: {v1|v2:
    {upload_bytes, download_broadcast, download_per_client}}}}),
    ``socket_latency_s`` ({inprocess, socket, workers})."""
    import statistics

    import jax

    from repro.fl.obs import RunRecorder
    from repro.fl.runtime import CodecConfig, Engine, RuntimeConfig
    from repro.fl.transport import TransportEngine
    from repro.launch import fed_train

    scen_kw = dict(dataset="synthmnist", clients=clients, clauses=16,
                   seed=0, rounds=rounds, local_epochs=1)
    _, data, _, _, _ = fed_train.build_scenario(**scen_kw)
    key = jax.random.PRNGKey(0)

    codec_grid = {
        "float32": {"v1": CodecConfig("float32")},
        "int8": {"v1": CodecConfig("int8"),
                 "v2": CodecConfig("int8", error_feedback=True)},
        "int4": {"v1": CodecConfig("int4"),
                 "v2": CodecConfig("int4", error_feedback=True)},
        "int8_sparse": {"v1": CodecConfig("int8", sparse=True),
                        "v2": CodecConfig("int8", sparse=True,
                                          error_feedback=True,
                                          index_coding="vrle")},
    }
    out = {"dataset": "synthmnist", "n_clients": clients,
           "rounds": rounds, "wire_bytes": {}, "socket_latency_s": {}}
    for strat_name in ("tpfl", "fedavg", "flis_dc"):
        out["wire_bytes"][strat_name] = {}
        for label, variants in codec_grid.items():
            out["wire_bytes"][strat_name][label] = {}
            for variant, ccfg in variants.items():
                strat = fed_train.build_scenario(
                    **{**scen_kw, "strategy": strat_name})[4]
                eng = Engine(strat, data,
                             RuntimeConfig(rounds=rounds, codec=ccfg))
                _, reps = eng.run(key)
                last = reps[-1]
                out["wire_bytes"][strat_name][label][variant] = {
                    "upload_bytes": last.upload_bytes,
                    "download_broadcast": last.download_bytes_broadcast,
                    "download_per_client": last.download_bytes_per_client,
                }
                print(f"bench_wire_bytes,{last.upload_bytes},"
                      f"strategy={strat_name}/codec={label}/{variant}",
                      flush=True)

    def _round_median(run_fn):
        rec = RunRecorder()          # in-memory: per-round phase spans
        run_fn(rec)
        spans = [ev["phases"]["round"] for ev in rec.history
                 if ev.get("phases") and "round" in ev["phases"]]
        return round(statistics.median(spans), 4)

    _, data2, _, _, strat = fed_train.build_scenario(**scen_kw)
    out["socket_latency_s"]["inprocess"] = _round_median(
        lambda rec: Engine(strat, data2, RuntimeConfig(rounds=rounds),
                           telemetry=rec).run(key))
    out["socket_latency_s"]["workers"] = socket_workers
    out["socket_latency_s"]["socket"] = _round_median(
        lambda rec: TransportEngine(
            strat, data2,
            RuntimeConfig(rounds=rounds, transport="socket",
                          workers=socket_workers),
            telemetry=rec, spec={"scenario": scen_kw}).run(key))
    print(f"bench_wire_latency,"
          f"{out['socket_latency_s']['socket']*1e6:.0f},"
          f"socket_vs_inprocess="
          f"{out['socket_latency_s']['socket']:.3f}s/"
          f"{out['socket_latency_s']['inprocess']:.3f}s", flush=True)
    ART.mkdir(exist_ok=True)
    (ART / "BENCH_wire_bytes.json").write_text(json.dumps(out, indent=2))
    return out


def main() -> None:
    from repro.data.ingest import registry as datasets
    from repro.launch import compile_cache

    compile_cache.configure()

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--mesh", action="store_true",
                    help="run table4/table5 federations shard-mapped "
                         "over a clients mesh of all visible devices")
    ap.add_argument("--datasets", default="synthmnist,synthfashion",
                    help="comma-separated table4 dataset flavours "
                         f"(registry names: {', '.join(datasets.names())};"
                         " table5 uses the first)")
    ap.add_argument("--data-dir", default=None,
                    help="ingest cache for table4/table5 (offline mirror"
                         " / real IDX+LEAF files — docs/datasets.md); "
                         "required for the real flavours")
    ap.add_argument("--encoding", default="bool",
                    help="feature encoding spec, e.g. bool | "
                         "thermometer:4 | quantile:8")
    ap.add_argument("--emit-bench", action="store_true",
                    help="only run the round-latency bench: per "
                         "strategy (and per tm_backend — ref and "
                         "pallas — for tpfl/fedtm), 1 warm-up round "
                         "then the median of 5 perf_counter-timed, "
                         "block_until_ready-fenced sync rounds, plus "
                         "the per-phase wall-time breakdown from the "
                         "telemetry tracer — written to artifacts/"
                         "BENCH_round_latency.json (rounds_timed, "
                         "warmup_rounds, round_wall_s, phase_wall_s, "
                         "both keyed {strategy: {tm_backend: ...}}; "
                         "the conformance-mesh-8 CI artifact); also "
                         "emits the client-scale trajectory")
    ap.add_argument("--emit-client-scale", action="store_true",
                    help="only run the client-scale bench: mmap-store "
                         "engine over a streamed synthfemnist "
                         "population, K active of N total — per N, "
                         "1 warm-up round then the median of 2 "
                         "perf_counter-timed rounds plus the store's "
                         "host-I/O byte gauges — written to artifacts/"
                         "BENCH_client_scale.json (the client-scale "
                         "CI artifact)")
    ap.add_argument("--emit-serve-bench", action="store_true",
                    help="only run the serving-plane bench: train a "
                         "small TPFL population, publish its checkpoint "
                         "into a registry, then serve mixed-cluster "
                         "batches per TM backend (ref, pallas) across a "
                         "batch-size sweep — p50/p99 batch latency and "
                         "sustained requests/sec — written to artifacts/"
                         "BENCH_serve_latency.json (the serve CI "
                         "artifact)")
    ap.add_argument("--emit-wire-bench", action="store_true",
                    help="only run the wire-cost bench: bytes/round per "
                         "strategy × codec × compression-v2 on/off "
                         "(error-feedback residuals, varint+RLE sparse "
                         "indices), plus socket-transport round latency "
                         "vs in-process — written to artifacts/"
                         "BENCH_wire_bytes.json (the transport CI "
                         "artifact)")
    ap.add_argument("--client-scale-ns", default=None,
                    help="comma-separated population sizes for the "
                         "client-scale bench (default "
                         "1000,100000,1000000; --quick default "
                         "1000,10000)")
    args = ap.parse_args()
    backend = "shardmap" if args.mesh else "inprocess"
    wanted = [n.strip() for n in args.datasets.split(",") if n.strip()]
    if not wanted:
        ap.error("--datasets needs at least one registry name")
    try:
        table_datasets = tuple(datasets.get(n).name for n in wanted)
    except ValueError as e:
        ap.error(str(e))
    if args.data_dir is None:
        file_backed = [n for n in table_datasets
                       if n in datasets.REAL_DATASETS]
        if file_backed:
            ap.error(f"--data-dir is required for the real flavours: "
                     f"{', '.join(file_backed)}")

    scale = common.Scale(n_clients=10, n_train=40, n_test=20, n_conf=20,
                         rounds=2, local_epochs=1) if args.quick \
        else common.Scale()

    if args.client_scale_ns is not None:
        scale_ns = tuple(int(s) for s in args.client_scale_ns.split(","))
    else:
        scale_ns = (1_000, 10_000) if args.quick \
            else (1_000, 100_000, 1_000_000)

    if args.emit_client_scale:
        print("name,us_per_call,derived")
        emit_client_scale(ns=scale_ns)
        return

    if args.emit_wire_bench:
        print("name,us_per_call,derived")
        emit_wire_bench(rounds=2 if args.quick else 3)
        return

    if args.emit_serve_bench:
        print("name,us_per_call,derived")
        emit_serve_bench(table_datasets[0], scale,
                         data_dir=args.data_dir, encoding=args.encoding,
                         requests_timed=5 if args.quick else 10)
        return

    if args.emit_bench:
        print("name,us_per_call,derived")
        emit_bench(table_datasets[0], scale, backend,
                   data_dir=args.data_dir, encoding=args.encoding)
        emit_client_scale(ns=scale_ns)
        return

    print("name,us_per_call,derived")
    for row in kernel_bench.run():
        print(row)

    t0 = time.time()
    rows4 = table4_tpfl.run(datasets=table_datasets, scale=scale,
                            backend=backend, data_dir=args.data_dir,
                            encoding=args.encoding)
    print(f"table4_tpfl,{(time.time()-t0)*1e6/max(len(rows4),1):.0f},"
          f"rows={len(rows4)}")

    t0 = time.time()
    rows5 = table5_comparison.run(dataset=table_datasets[0], scale=scale,
                                  backend=backend, data_dir=args.data_dir,
                                  encoding=args.encoding)
    best = max(rows5, key=lambda r: r["accuracy"])
    print(f"table5_comparison,{(time.time()-t0)*1e6/max(len(rows5),1):.0f},"
          f"best={best['method']}:{best['accuracy']}")

    t0 = time.time()
    conv = convergence.run(scale=common.Scale(
        rounds=2 if args.quick else 3,
        n_clients=scale.n_clients, n_train=scale.n_train,
        n_test=scale.n_test, n_conf=scale.n_conf,
        local_epochs=scale.local_epochs))
    print(f"convergence,{(time.time()-t0)*1e6:.0f},"
          f"exp5_first_round_max={conv['claim_exp5_first_round_is_max']}")

    t0 = time.time()
    abl = ablation_multiclass.run(scale=common.Scale(
        rounds=2 if args.quick else 3,
        n_clients=scale.n_clients, n_train=scale.n_train,
        n_test=scale.n_test, n_conf=scale.n_conf,
        local_epochs=scale.local_epochs))
    print(f"ablation_multiclass,{(time.time()-t0)*1e6/3:.0f},"
          f"best_j={max(abl, key=lambda r: r['accuracy'])['top_classes']}")


if __name__ == "__main__":
    main()
