"""Federated serving driver: personalized inference as a service.

The deployment half of TPFL — ``fed_train`` leaves a population of
personalized models behind (a round checkpoint, optionally an mmap
client store); this driver stands up the serving plane over them:

1. **Publish.**  The newest checkpoint under ``--ckpt-dir`` is placed
   into the ``--registry`` as an immutable version (sha256
   verify-then-place, atomic rename, sidecar last; the checkpoint
   directory's ``manifest.json`` rides along as provenance).
2. **Activate.**  The plane pulls the latest registry version —
   sidecar-verified, then decoded against this process's engine-state
   template, so a corrupted payload, flipped sidecar, or layout drift
   (different strategy / slot count / clause count) is refused loudly
   before a single request is answered.
3. **Serve.**  ``--requests`` batches of ``--batch`` requests each,
   round-robin over the client population so every batch mixes
   clusters; each batch is ONE ``predict_batched`` call (a single
   fused-votes kernel launch under ``--tm-backend pallas``).  Between
   batches the plane polls ``refresh()`` — a newer version published
   mid-serving warm-swaps in atomically (in-flight batches finish on
   the old version).

The scenario flags (``--dataset --clients --clauses --seed ...``) must
repeat the training run's: they rebuild the same partition, strategy
template, and per-client init chain the checkpoint was written under
(``launch.fed_train.build_scenario`` is shared by both drivers).  With
``--client-store mmap --store-dir`` pointing at the training store,
spilled rows serve each client's own personalized model and
never-sampled clients fall back to their deterministic init — exactly
what offline evaluation resolves.  ``--verify-offline`` proves it:
every client's served prediction is compared bit-for-bit against an
unbatched offline prediction from its resolved row, and the process
exits nonzero on any mismatch.

Not to be confused with ``repro.launch.serve`` (the *transformer*
decode demo driving the unified KV-cache protocol) — this is the
federated plane.  See ``docs/serving.md``.

  PYTHONPATH=src python -m repro.launch.fed_serve \\
      --ckpt-dir runs/ckpt --clients 20 --batch 32 --requests 8 \\
      --verify-offline
"""
from __future__ import annotations

import pathlib
import statistics
import time

import jax
import numpy as np


def _offline_predict(strategy, row, x) -> np.ndarray:
    """Unbatched reference prediction for ONE client's resolved row —
    the offline path served predictions must match bit-for-bit.  TM
    strategies go through :func:`repro.core.tm.predict` (which honours
    ``use_kernel``); MLP rows through an argmax over
    :func:`repro.core.mlp.apply`."""
    import jax.numpy as jnp

    from repro.core import mlp, tm

    if getattr(strategy, "tm_cfg", None) is not None:
        return np.asarray(tm.predict(row, x, strategy.tm_cfg))
    params = getattr(row, "params", row)   # FLIS wraps the MLP
    return np.asarray(jnp.argmax(mlp.apply(params, x), axis=-1))


def main(argv: list[str] | None = None) -> dict:
    import argparse

    from repro.data.ingest import registry as datasets
    from repro.fl.runtime import (CodecConfig, Engine, RuntimeConfig,
                                  checkpointing)
    from repro.fl.serve import ModelRegistry, ServeTelemetry, ServingPlane
    from repro.launch import compile_cache
    from repro.launch.fed_train import STRATEGY_CHOICES, build_scenario

    compile_cache.configure()

    ap = argparse.ArgumentParser(
        description="Federated serving plane: personalized inference "
                    "from a versioned model registry")
    # scenario — must match the training run (rebuilds its layout)
    ap.add_argument("--strategy", default="tpfl",
                    choices=STRATEGY_CHOICES)
    ap.add_argument("--dataset", default="synthmnist",
                    choices=datasets.names())
    ap.add_argument("--data-dir", default=None)
    ap.add_argument("--encoding", default="bool", metavar="SPEC")
    ap.add_argument("--experiment", type=int, default=5)
    ap.add_argument("--writers", type=int, default=None)
    ap.add_argument("--clients", type=int, default=20)
    ap.add_argument("--clauses", type=int, default=48)
    ap.add_argument("--local-epochs", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-slots", type=int, default=8)
    ap.add_argument("--probe-size", type=int, default=64)
    # structural knobs that shape the checkpointed engine state
    ap.add_argument("--codec", default="float32",
                    choices=("float32", "int8", "int4"))
    ap.add_argument("--sparse", action="store_true")
    ap.add_argument("--buffer-capacity", type=int, default=64)
    ap.add_argument("--tm-backend", default="ref",
                    choices=("ref", "pallas"),
                    help="TM inference path: pallas serves each "
                         "mixed-cluster batch as one fused-votes "
                         "kernel launch (bit-identical to ref)")
    ap.add_argument("--client-store", default="resident",
                    dest="client_store", choices=("resident", "mmap"))
    ap.add_argument("--store-dir", default=None, metavar="DIR",
                    help="the training run's client-store root — "
                         "spilled rows serve personalized models, "
                         "unwritten rows fall back to deterministic "
                         "init (mmap only)")
    # registry / serving
    ap.add_argument("--ckpt-dir", default=None,
                    help="training checkpoint directory; its newest "
                         "round is published into the registry at "
                         "startup")
    ap.add_argument("--registry", default=None, metavar="DIR",
                    help="registry root (default: <ckpt-dir>/registry)")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--requests", type=int, default=8,
                    help="number of batches to serve")
    ap.add_argument("--verify-offline", action="store_true",
                    help="after serving, check every client's served "
                         "prediction bit-for-bit against its resolved "
                         "row's offline prediction; exit 1 on mismatch")
    ap.add_argument("--telemetry-dir", default=None, metavar="RUN_DIR",
                    help="write serve_events.jsonl (per-batch latency "
                         "and spans, swap/publish events) there")
    args = ap.parse_args(argv)

    if args.registry is None and args.ckpt_dir is None:
        raise SystemExit("need --registry and/or --ckpt-dir: nowhere "
                         "to pull a model from")
    registry_root = args.registry or str(
        pathlib.Path(args.ckpt_dir) / "registry")

    pool, data, tm_cfg, fed_cfg, strategy = build_scenario(
        dataset=args.dataset, data_dir=args.data_dir,
        encoding=args.encoding, clients=args.clients,
        clauses=args.clauses, seed=args.seed,
        experiment=args.experiment, writers=args.writers,
        local_epochs=args.local_epochs, strategy=args.strategy,
        max_slots=args.max_slots, probe_size=args.probe_size)

    rt_cfg = RuntimeConfig(
        codec=CodecConfig(args.codec, sparse=args.sparse),
        buffer_capacity=args.buffer_capacity,
        tm_backend=args.tm_backend,
        client_store=args.client_store, store_dir=args.store_dir)
    engine = Engine(strategy, data, rt_cfg)
    # the engine's key chain is k_init, k_rounds = split(PRNGKey(seed));
    # serving re-derives k_init so an mmap store's never-spilled rows
    # fault in exactly as the training run would have generated them
    k_init, _ = jax.random.split(jax.random.PRNGKey(args.seed))
    like = engine.init(k_init)

    telemetry = ServeTelemetry(args.telemetry_dir) \
        if args.telemetry_dir else None
    registry = ModelRegistry(registry_root)
    if args.ckpt_dir:
        newest = checkpointing.latest(args.ckpt_dir)
        if newest is not None:
            version = registry.publish(newest)
            if telemetry is not None:
                telemetry.publish_event(version, registry.path_for(version))
            print(f"published {newest} as registry version {version}",
                  flush=True)
    if registry.latest() is None:
        raise SystemExit(f"registry {registry_root} is empty and "
                         f"--ckpt-dir offered no checkpoint to publish")

    plane = ServingPlane(engine.strategy, registry, like,
                         store=engine.store, telemetry=telemetry)
    plane.refresh()
    n = args.clients
    n_test = int(np.asarray(data.x_test).shape[1])
    print(f"serving {args.strategy} version {plane.active_version} "
          f"[{args.tm_backend}] over {n} clients "
          f"(store={args.client_store}): {args.requests} batches of "
          f"{args.batch}", flush=True)

    x_test = np.asarray(data.x_test)
    latencies = []
    for r in range(args.requests):
        # stride-round-robin over the population: consecutive lanes hit
        # different clients, so every batch mixes clusters
        ids = (np.arange(args.batch) * 7 + r) % n
        x = x_test[ids, (r + np.arange(args.batch)) % n_test]
        t0 = time.perf_counter()
        preds = plane.predict(ids, x)
        latencies.append(time.perf_counter() - t0)
        del preds
        plane.refresh()   # a newer published version warm-swaps here

    lat = sorted(latencies)
    p50 = statistics.median(lat)
    p99 = lat[min(len(lat) - 1, int(round(0.99 * (len(lat) - 1))))]
    served = args.requests * args.batch
    total = sum(lat)
    rps = served / total if total > 0 else float("inf")
    print(f"served {served} requests in {total * 1e3:.1f}ms: "
          f"{rps:.0f} req/s, p50={p50 * 1e6:.0f}us "
          f"p99={p99 * 1e6:.0f}us per batch", flush=True)

    result = {"version": plane.active_version, "requests": served,
              "requests_per_s": rps, "p50_s": p50, "p99_s": p99}

    if args.verify_offline:
        # one covering batch: every client once, each with its own
        # test sample — served predictions must equal the offline
        # (unbatched, per-client) predictions of the resolved rows
        ids = np.arange(n)
        x = x_test[:, 0]
        got = plane.predict(ids, x)
        state = registry.pull(plane.active_version, like)
        rows, _ = plane._resolve_rows(state, ids)
        mismatch = 0
        for c in range(n):
            row = jax.tree_util.tree_map(lambda a: a[c], rows)
            want = _offline_predict(engine.strategy, row, x[c:c + 1])[0]
            if int(want) != int(got[c]):
                mismatch += 1
                print(f"client {c}: served {int(got[c])}, "
                      f"offline {int(want)}", flush=True)
        result["verified_clients"] = n
        result["mismatches"] = mismatch
        if mismatch:
            raise SystemExit(
                f"serving parity FAILED: {mismatch}/{n} clients differ "
                f"from offline predictions")
        print(f"offline parity: OK ({n} clients bit-identical)",
              flush=True)
    return result


if __name__ == "__main__":
    main()
