#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, and the serving
cell's knee; run on the chip, one process per call.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1,2,3 \\
        [--control] [--fault half] [--sweep 300,400,500 --sweep-seconds 8]

For each seed it prints one JSON line: the program's readings against
the plain reference, and with ``--control`` the control's (the reference
in the precision below the configuration's, put in the program's
place).  Round cells also print the fault ``unchanged`` (the initial
state handed back as the round's result) and, with ``--fault half``,
the program with half of each round's uploads left out of the
aggregation.  ``--sweep`` offers the serving cell each rate for
``--sweep-seconds`` and prints the tail, the answered rate and how the
latency grew over the window.
"""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import time

import harness


def _ctx(name: str, seed: int, seconds: float = 5.0,
         overrides: tuple[dict, dict] = ({}, {})) -> harness.Context:
    """The cell's context; ``overrides`` (configuration, traffic) let the
    tests take the same readings at a small size on the CPU."""
    bench = harness.load_benchmark()
    cell = harness.cell(bench, name)
    work = harness.WORK_DIR / f"calibrate-{name}"
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    return harness.Context(
        cell=cell, config={**harness.load_config(cell["config"]),
                           **overrides[0]},
        traffic={**harness.load_traffic(cell["traffic"]), **overrides[1]},
        seed=seed, seconds=seconds, trace=False, t0=time.perf_counter(),
        work_dir=work)


def _half_aggregation():
    """Fault: the aggregation sees only the first half of the cohort."""
    import jax.numpy as jnp
    from repro.fl.runtime import executors
    orig = executors.InProcessExecutor.masked_mean

    def masked_mean(self, strategy, dec, slots, arrive):
        k = arrive.shape[0]
        keep = jnp.arange(k) < k // 2
        return orig(self, strategy, dec, slots, arrive & keep)

    executors.InProcessExecutor.masked_mean = masked_mean
    return lambda: setattr(executors.InProcessExecutor, "masked_mean", orig)


def round_seed(name: str, seed: int, control: bool, fault: str | None,
               overrides: tuple[dict, dict] = ({}, {})) -> dict:
    import jax.numpy as jnp
    rnd = harness.load_module("drivers", "round")
    ctx = _ctx(name, seed, overrides=overrides)
    cfg = ctx.config
    ref = harness.load_module("reference", cfg["reference"])
    t = time.perf_counter()
    s = rnd.Setup(ctx)
    snaps, inputs = s.snaps, s.inputs()
    del s
    gc.collect()
    out = {"seed": seed, "setup_s": time.perf_counter() - t}
    key = jnp.asarray(harness.key_data(seed))
    n_rounds = ctx.traffic["check_rounds"]
    t = time.perf_counter()
    want = ref.run_rounds(key, inputs, cfg, cfg["clients_per_round"],
                          n_rounds)
    out["reference_s"] = time.perf_counter() - t
    out["program"] = ref.compare(snaps, want, cfg)
    if "w" in snaps[-1]:
        # float32 sums of weights above 256 round on a bfloat16 pass
        out["max_weight"] = int(max(x["w"].max() for x in snaps[1:]))
    if cfg["model"] == "mlp":
        out["unchanged"] = ref.compare(
            [snaps[0]] + [dict(snaps[0], acc=x["acc"]) for x in snaps[1:]],
            want, cfg)
    else:
        import jax
        import numpy as np
        ta0, w0 = ref.init_population(jax.random.split(key)[0],
                                      inputs["x_train"].shape[0],
                                      ref.Widths(cfg))
        init = {"ta": np.asarray(ta0), "w": np.asarray(w0)}
        out["unchanged"] = ref.compare(
            [{}] + [dict(x, **init) for x in snaps[1:]], want, cfg)
    del snaps
    if control:
        t = time.perf_counter()
        ctrl = ref.run_rounds(key, inputs, cfg, cfg["clients_per_round"],
                              n_rounds, control=True)
        out["control_s"] = time.perf_counter() - t
        out["control"] = ref.compare(ctrl, want, cfg)
        del ctrl
    if fault == "half":
        undo = _half_aggregation()
        try:
            s = rnd.Setup(_ctx(name, seed, overrides=overrides))
            out["fault_half"] = ref.compare(s.snaps, want, cfg)
            del s
        finally:
            undo()
    gc.collect()
    shutil.rmtree(ctx.work_dir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return out


def serve_seed(name: str, seed: int, control: bool, seconds: float,
               overrides: tuple[dict, dict] = ({}, {})) -> dict:
    import numpy as np
    srv = harness.load_module("drivers", "serve")
    ctx = _ctx(name, seed, seconds, overrides)
    plane, x_test, setup_s = _plane(ctx)
    sched = srv.schedule(seed, ctx.traffic, seconds,
                         ctx.config["population"], x_test.shape[1])
    win = srv.serve_window(plane, sched, x_test, ctx.traffic["max_batch"])
    del plane
    gc.collect()
    idx = srv.sample_answered(seed, win, ctx.traffic["check_sample"])
    want = srv.reference_predictions(ctx, sched, idx, x_test)
    out = {"seed": seed, "setup_s": setup_s, "compared": int(idx.size),
           "program": {"mismatches": int(np.sum(win["preds"][idx] != want))},
           **{k: v for k, v in srv.window_metrics(win, seconds).items()
              if k != "gen_lag_s"}}
    if control:
        for kind in ("bf16", "other_client"):
            ctl = srv.reference_predictions(ctx, sched, idx, x_test,
                                            control=kind)
            out[f"control_{kind}"] = {
                "mismatches": int(np.sum(ctl != want))}
    shutil.rmtree(ctx.work_dir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return out


def _plane(ctx):
    srv = harness.load_module("drivers", "serve")
    t = time.perf_counter()
    plane, x_test = srv.build_plane(ctx)
    return plane, x_test, time.perf_counter() - t


def sweep(name: str, rates: list[float], seconds: float):
    import numpy as np
    srv = harness.load_module("drivers", "serve")
    ctx = _ctx(name, 1, seconds)
    plane, x_test, _ = _plane(ctx)
    for rate in rates:
        tr = dict(ctx.traffic, rate_rps=rate)
        sched = srv.schedule(7, tr, seconds, ctx.config["population"],
                             x_test.shape[1])
        win = srv.serve_window(plane, sched, x_test, tr["max_batch"])
        lat = win["done"] - win["due"]
        q = max(1, lat.size // 4)
        m = srv.window_metrics(win, seconds)
        print(json.dumps({
            "rate": rate, "serve_p95_ms": m["serve_p95_ms"],
            "serve_rps": m["serve_rps"], "failed": m["failed"],
            "first_quarter_ms": float(np.nanmean(lat[:q]) * 1e3),
            "last_quarter_ms": float(np.nanmean(lat[-q:]) * 1e3),
            "mean_batch": float(np.mean([c[0] for c in win["calls"]])),
            "mean_call_ms": float(np.mean([c[2] for c in win["calls"]])
                                  * 1e3)}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", choices=("half",), default=None)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--sweep", default="")
    ap.add_argument("--sweep-seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(harness.ROOT / "src"))
    from repro.launch import compile_cache
    compile_cache.configure()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.default_backend() != "tpu":
        print("calibrate.py: readings are taken on the chip only",
              file=sys.stderr)
        return 3
    driver = harness.load_traffic(harness.cell(
        harness.load_benchmark(), args.workload)["traffic"])["driver"]
    if args.sweep:
        sweep(args.workload, [float(r) for r in args.sweep.split(",")],
              args.sweep_seconds)
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        if driver == "round":
            round_seed(args.workload, seed, args.control, args.fault)
        else:
            serve_seed(args.workload, seed, args.control, args.seconds)
    shutil.rmtree(harness.WORK_DIR, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
