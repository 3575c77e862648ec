#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <run_seconds> --trace <0|1>

The cell, its configuration and its traffic mix are found by name in
``BENCHMARK.json``; the traffic file names its ``drivers/`` module.  ``--trace 0``
prints the cell's end-to-end metrics, ``--trace 1`` its per-layer
metrics, each read by ``metrics/<name>.py``.  The last line of standard
output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and last
``checks``: each number compared beside its limit); the checks are also
the last lines of standard error.  With no TPU, or fewer chips than the
cell asks for, it exits nonzero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import harness  # noqa: E402


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _fail(msg: str, code: int) -> int:
    print(f"run.py: {msg} — no result", file=sys.stderr)
    return code


def main(argv=None, *, require_tpu: bool = True, config_overrides=None,
         traffic_overrides=None) -> int:
    """``require_tpu``, ``config_overrides`` and ``traffic_overrides``
    exist for the harness's own tests on the CPU at a small size."""
    args = _args(argv)
    src = harness.ROOT / "src"
    if not (src / "repro").is_dir():
        return _fail(f"the program is not in this checkout ({src})", 2)
    try:
        bench = harness.load_benchmark()
        cell = harness.cell(bench, args.workload)
        config = {**harness.load_config(cell["config"]),
                  **(config_overrides or {})}
        traffic = {**harness.load_traffic(cell["traffic"]),
                   **(traffic_overrides or {})}
        driver = harness.load_module("drivers", traffic["driver"])
    except (OSError, KeyError) as e:
        return _fail(str(e), 2)

    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    from repro.launch import compile_cache
    compile_cache.configure()
    import jax
    # small eager programs too, so that a second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    if require_tpu:
        if jax.default_backend() != "tpu":
            return _fail(f"JAX's backend is {jax.default_backend()!r}, "
                         "not 'tpu'", 3)
        if len(jax.devices()) < cell["chips"]:
            return _fail(f"the cell needs {cell['chips']} chips, JAX "
                         f"sees {len(jax.devices())}", 3)

    work = harness.WORK_DIR / args.workload
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    ctx = harness.Context(cell=cell, config=config, traffic=traffic,
                          seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), t0=T0, work_dir=work)
    try:
        outcome = driver.run(ctx)
    finally:
        shutil.rmtree(work, ignore_errors=True)

    metrics = {}
    if args.trace:
        for m in harness.per_layer_for(bench, args.workload):
            value = harness.load_module("metrics", m["name"]).read(
                outcome.records)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in harness.end_to_end_for(bench, args.workload):
            metrics[m["name"]] = {"value": outcome.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": outcome.memory_peak_bytes}
    if args.trace:
        device["busy_s"] = outcome.device_busy_s
        device["window_s"] = outcome.window_s
    print("setup parts: " + json.dumps(ctx.setup_parts), file=sys.stderr)
    for line in harness.check_lines(outcome.checks):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(harness.result_line(outcome, metrics, device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
