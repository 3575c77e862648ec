#!/usr/bin/env python3
"""``calibrate.py``'s round readings for the cells of the ``round_mesh``
driver, whose engine spans a mesh of chips; run on the chip.

    python3 benchmarks/chip/calibrate_mesh.py --workload <cell> \\
        --seeds 1,2,3 [--control] [--reference-only]

For each seed it prints one JSON line: the program's readings against
the plain reference (the shard-mapped engine through the checked rounds,
``drivers/round_mesh.py``'s set-up), the fault ``unchanged`` (the
initial state handed back as each round's result) and, with
``--control``, the control's (the reference drawing in bfloat16, put in
the program's place).  ``--reference-only`` leaves the program out: the
reference, the control and the fault run on one chip, so a host with
one chip can take those readings.
"""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import time

import calibrate
import harness


def round_seed(name: str, seed: int, control: bool, program: bool = True,
               overrides: tuple[dict, dict] = ({}, {})) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import scenario

    ctx = calibrate._ctx(name, seed, overrides=overrides)
    cfg = ctx.config
    ref = harness.load_module("reference", cfg["reference"])
    out = {"seed": seed}
    t = time.perf_counter()
    if program:
        s = harness.load_module("drivers", "round_mesh").Setup(ctx)
        snaps, inputs = s.snaps, s.inputs()
        del s
    else:
        data = scenario.client_data(ctx)
        inputs = {k: jax.device_get(getattr(data, k)) for k in
                  ("x_train", "y_train", "x_test", "y_test", "x_conf")}
        del data
    gc.collect()
    out["setup_s"] = time.perf_counter() - t
    key = jnp.asarray(harness.key_data(seed))
    n_rounds = ctx.traffic["check_rounds"]
    t = time.perf_counter()
    want = ref.run_rounds(key, inputs, cfg, cfg["clients_per_round"],
                          n_rounds)
    out["reference_s"] = time.perf_counter() - t
    if program:
        out["program"] = ref.compare(snaps, want, cfg)
        out["max_weight"] = int(max(x["w"].max() for x in snaps[1:]))
        del snaps
    ta0, w0 = ref.init_population(jax.random.split(key)[0],
                                  inputs["x_train"].shape[0],
                                  ref.Widths(cfg))
    init = {"ta": np.asarray(ta0), "w": np.asarray(w0)}
    out["unchanged"] = ref.compare(
        [{}] + [dict(x, **init) for x in want[1:]], want, cfg)
    if control:
        t = time.perf_counter()
        ctrl = ref.run_rounds(key, inputs, cfg, cfg["clients_per_round"],
                              n_rounds, control=True)
        out["control_s"] = time.perf_counter() - t
        out["control"] = ref.compare(ctrl, want, cfg)
    shutil.rmtree(ctx.work_dir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--reference-only", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(harness.ROOT / "src"))
    from repro.launch import compile_cache
    compile_cache.configure()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.default_backend() != "tpu":
        print("calibrate_mesh.py: readings are taken on the chip only",
              file=sys.stderr)
        return 3
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        round_seed(args.workload, seed, args.control,
                   program=not args.reference_only)
    shutil.rmtree(harness.WORK_DIR, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
