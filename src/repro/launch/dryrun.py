import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")

"""Multi-pod dry-run: lower + compile every (architecture × input shape ×
mesh) combination on the production mesh, capture memory/cost analysis and
the collective schedule, and write one JSON artifact per combination.

MUST be run as its own process (the XLA_FLAGS line above executes before
any other import — jax locks the device count on first init).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all
"""

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402
from pathlib import Path  # noqa: E402

import jax           # noqa: E402

from repro.configs import registry                      # noqa: E402
from repro.launch import hlo_analysis, steps            # noqa: E402
from repro.launch.mesh import make_production_mesh       # noqa: E402
from repro.launch.peaks import V5E, peaks               # noqa: E402

ARTIFACTS = Path(__file__).resolve().parents[3] / "benchmarks" / "artifacts"


def dryrun(arch: str, shape_name: str, multi_pod: bool = False,
           save: bool = True, extra_tag: str = "",
           opt_dtype: str = "f32") -> dict:
    import jax.numpy as jnp
    from repro.optim import adamw
    cfg = registry.get(arch)
    shape = steps.SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    opt_cfg = adamw.AdamWConfig(
        state_dtype=jnp.bfloat16 if opt_dtype == "bf16" else jnp.float32)

    t0 = time.time()
    ins = steps.input_specs(cfg, shape, mesh, opt_cfg)

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            step = steps.make_train_step(cfg, opt_cfg)
            lowered = jax.jit(step).lower(ins["params"], ins["opt_state"],
                                          ins["batch"])
        elif shape.kind == "prefill":
            step = steps.make_prefill_step(cfg)
            lowered = jax.jit(step).lower(ins["params"], ins["batch"])
        else:
            step = steps.make_serve_step(cfg, window=ins["window"])
            lowered = jax.jit(step).lower(ins["params"], ins["token"],
                                          ins["caches"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = hlo_analysis.collective_bytes(compiled.as_text())

    n_params = cfg.param_count()
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6.0 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2.0 * n_active * tokens
    else:
        tokens = shape.global_batch           # one token per sequence
        model_flops = 2.0 * n_active * tokens

    chip = peaks(V5E)
    rf = hlo_analysis.roofline(
        cost, coll, peak_flops=chip.bf16_flops, hbm_bw=chip.hbm_bw,
        ici_bw=chip.ici_bw_per_link, model_flops=model_flops, chips=chips,
        arg_bytes=mem.argument_size_in_bytes)

    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "kind": shape.kind,
        "params": n_params,
        "active_params": n_active,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes_per_device": (mem.argument_size_in_bytes
                                      + mem.temp_size_in_bytes),
        },
        "roofline": rf,
    }
    if save:
        ARTIFACTS.mkdir(parents=True, exist_ok=True)
        tag = f"{arch}_{shape_name}_{result['mesh']}" + \
            (f"_{extra_tag}" if extra_tag else "")
        (ARTIFACTS / f"dryrun_{tag}.json").write_text(
            json.dumps(result, indent=2))
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every arch × shape on this mesh")
    ap.add_argument("--tag", default="", help="artifact suffix for perf runs")
    ap.add_argument("--opt-dtype", default="f32", choices=["f32", "bf16"])
    args = ap.parse_args()

    archs = list(registry.ARCHS) if args.arch in ("all",) or args.all \
        else [args.arch]
    shapes = list(steps.SHAPES) if args.shape in ("all",) or args.all \
        else [args.shape]

    failures = []
    for arch in archs:
        for shape in shapes:
            try:
                r = dryrun(arch, shape, multi_pod=args.multi_pod,
                           extra_tag=args.tag,
                           opt_dtype=args.opt_dtype)
                rf = r["roofline"]
                print(f"OK   {arch:24s} {shape:12s} {r['mesh']:8s} "
                      f"compile={r['compile_s']:.0f}s "
                      f"comp={rf['compute_s']:.2e}s "
                      f"mem={rf['memory_s']:.2e}s "
                      f"coll={rf['collective_s']:.2e}s "
                      f"bound={rf['bottleneck']}", flush=True)
            except Exception as e:  # noqa: BLE001
                failures.append((arch, shape, repr(e)))
                print(f"FAIL {arch:24s} {shape:12s}: {e!r}", flush=True)
                traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} dry-run failures: {failures}")


if __name__ == "__main__":
    main()
