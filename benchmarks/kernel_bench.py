"""TM kernel micro-bench: jnp oracle vs the Pallas kernels.

Times the pure-jnp clause-eval oracle against the clause-eval kernel,
and the fused train-epoch kernel against its reference-scan equivalent.
Every row names the device it ran on.  On the CPU backend the kernels
run in Pallas interpret mode: those rows check that the harness works
and say nothing about the chip, so no device projection is printed
beside them.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import tm
from repro.kernels import clause_eval, ops, ref


def bench(fn, *args, iters=5):
    out = fn(*args)                  # warm-up: compile + first run, once
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / iters * 1e6     # µs


def run() -> list[str]:
    dev = jax.devices()[0]
    mode = "interpret" if ops.interpret_mode() else "compiled"
    where = f"device={dev.platform}:{dev.device_kind},kernel={mode}"
    rows = []
    for (C, m, o, B) in [(10, 300, 784, 64), (62, 500, 784, 32)]:
        L = 2 * o
        key = jax.random.PRNGKey(0)
        include = jax.random.bernoulli(key, 0.1, (C * m, L)).astype(jnp.int8)
        lits = jax.random.bernoulli(key, 0.5, (B, L)).astype(jnp.int8)

        f = jax.jit(lambda i, l: ref.clause_outputs_ref(i, l))
        us = bench(f, include, lits)
        rows.append(f"clause_eval_C{C}_m{m}_B{B},{us:.1f},{where}")

        # big tiles keep an interpret-mode grid small; tile invariance is
        # pinned by tests/test_kernels.py, so the tiling is a free choice
        us_k = bench(lambda i, l: clause_eval.clause_outputs_pallas(
            i, l, bt=B, ct=512, lt=512, interpret=ops.interpret_mode()),
            include, lits)
        rows.append(f"clause_eval_pallas_C{C}_m{m}_B{B},{us_k:.1f},{where}")

    # fused train-epoch kernel vs the reference per-sample scan, at the
    # quick-bench federated scale (one round's client cohort)
    N, S, C, m, o = 10, 40, 10, 48, 100
    cfg = tm.TMConfig(n_classes=C, n_clauses=m, n_features=o,
                      n_states=63, s=5.0, T=40)
    kcfg = tm.TMConfig(n_classes=C, n_clauses=m, n_features=o,
                       n_states=63, s=5.0, T=40, use_kernel=True)
    key = jax.random.PRNGKey(1)
    params = jax.vmap(lambda k: tm.init_params(cfg, k))(
        jax.random.split(key, N))
    xs = (jax.random.uniform(jax.random.fold_in(key, 1),
                             (N, S, o)) < 0.5).astype(jnp.int32)
    ys = jax.random.randint(jax.random.fold_in(key, 2), (N, S), 0, C)
    keys = jax.random.split(jax.random.fold_in(key, 3), N)
    us_fused = bench(
        lambda p, x, y, k: tm.train_batched(p, x, y, k, kcfg),
        params, xs, ys, keys, iters=3)
    us_ref = bench(
        lambda p, x, y, k: tm.train_batched(p, x, y, k, cfg),
        params, xs, ys, keys, iters=3)
    rows.append(f"train_epoch_fused_N{N}_S{S}_C{C}_m{m},"
                f"{us_fused:.1f},ref_scan_us={us_ref:.1f},{where}")
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
