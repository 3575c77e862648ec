import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")

"""Dry-run of the paper's technique ON THE MESH: one TPFL round vs one
FedAvg-on-TM round, lowered+compiled for the production mesh at paper
scale (C=10, m=300 clauses, o=784 features, 256 clients sharded over the
FSDP axes).  The TPFL program is the *runtime engine's* shard-mapped
sync round (`repro.fl.runtime.executors.build_sharded_round` — the same
program `fed_train --mesh` executes, clients over the mesh's data axes,
aggregation one masked psum of the (C, m) accumulator); FedAvg-on-TM
keeps the legacy full-state tree-mean builder as the baseline.  The
collective-bytes delta between the two programs is the paper's
communication claim measured in the partitioned HLO.

Also lowered: the engine's *async* buffered update
(`build_sharded_async_update` — device-buffer insert, maturity gate,
staleness-discounted psum mean), priced on the same mesh, so the
heavy-traffic straggler regime has its collective bytes on record too.

  PYTHONPATH=src python -m repro.launch.fed_dryrun [--multi-pod]
"""

import argparse  # noqa: E402
import json      # noqa: E402
import time      # noqa: E402
from pathlib import Path  # noqa: E402

import jax       # noqa: E402

from repro.core import federation, tm                     # noqa: E402
from repro.launch import fed_train, hlo_analysis          # noqa: E402
from repro.launch.mesh import make_production_mesh       # noqa: E402
from repro.launch.peaks import V5E, peaks                # noqa: E402

ARTIFACTS = Path(__file__).resolve().parents[3] / "benchmarks" / "artifacts"


def client_scale(n_total: int = 1_000_000, k_active: int = 256) -> dict:
    """The million-client working set on the host side of the mesh round.

    The lowered shard-mapped round above prices K=256 active clients on
    the mesh; this section prices where those K rows *come from*: a
    memory-mapped :class:`~repro.fl.store.ClientStore` sized for
    N=1,000,000 simulated clients, strategy-faithful TM rows
    (``TPFLStrategy.init_cohort`` is the fault-in path, exactly as the
    mmap engine wires it), one K-active gather → mutate → spill → flush
    cycle timed end to end.  ``resident_bytes`` is the O(K) contract in
    numbers: only the sampled rows ever materialize, everything else is
    a hole in a sparse file."""
    import tempfile

    import numpy as np

    from repro.fl.runtime.strategy import TPFLStrategy
    from repro.fl.store import ClientStore

    tm_cfg = tm.TMConfig(n_classes=10, n_clauses=16, n_features=64,
                         n_states=63, s=5.0, T=16)
    strat = TPFLStrategy(tm_cfg, local_epochs=1)
    key = jax.random.PRNGKey(0)

    def init_fn(ids):
        return jax.tree.map(
            np.asarray, strat.init_cohort(key, np.asarray(ids), n_total))

    row = jax.tree.map(lambda a: a[0], init_fn(np.asarray([0])))
    store = ClientStore(tempfile.mkdtemp(prefix="dryrun_client_store_"),
                        n_total, {"cs": row}, init_fn=lambda ids:
                        {"cs": init_fn(ids)})
    ids = np.asarray(jax.random.choice(
        jax.random.PRNGKey(1), n_total, (k_active,), replace=False))
    t0 = time.time()
    bundle = store.gather(ids)                    # faults K rows in
    bundle = jax.tree.map(lambda a: (a + 1).astype(a.dtype), bundle)
    store.spill(ids, bundle)                      # round's writeback
    store.flush()
    wall = time.time() - t0
    back = store.gather(ids)                      # round-trip check
    ok = all(bool(np.array_equal(a, b)) for a, b in zip(
        jax.tree_util.tree_leaves(bundle), jax.tree_util.tree_leaves(back)))
    section = {
        "n_clients": n_total, "k_active": k_active,
        "row_bytes": store.row_nbytes,
        "resident_rows": store.written_count(),
        "resident_bytes": store.written_count() * store.row_nbytes,
        "gather_spill_s": round(wall, 3),
        "io_read_bytes": store.io_read_bytes,
        "io_written_bytes": store.io_written_bytes,
        "roundtrip_ok": ok,
    }
    print(f"client_scale: {k_active} of {n_total} rows resident "
          f"({section['resident_bytes']/1e6:.1f} MB of "
          f"{n_total*store.row_nbytes/1e9:.0f} GB virtual), "
          f"gather+spill {section['gather_spill_s']}s", flush=True)
    return section


def run(multi_pod: bool = False, n_clients: int = 256,
        clauses: int = 300, buffer_capacity: int = 512) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    tm_cfg = tm.TMConfig(n_classes=10, n_clauses=clauses, n_features=784,
                         n_states=127, s=10.0, T=1000)
    fed_cfg = federation.FedConfig(n_clients=n_clients, rounds=1,
                                   local_epochs=1)
    params, cw, data, key, keys, arrive, client_axes = \
        fed_train.abstract_round_inputs(tm_cfg, fed_cfg, mesh,
                                        n_train=64, n_test=32, n_conf=32)
    if client_axes is None:
        raise SystemExit(f"{n_clients} clients do not divide the mesh's "
                         f"FSDP axes — pick a multiple")

    from repro.fl.runtime.executors import (build_sharded_async_update,
                                            build_sharded_round)
    strategy = federation._strategy(tm_cfg, fed_cfg)
    engine_round = build_sharded_round(
        strategy, mesh, axis_name=client_axes, collective="psum",
        n_clients=n_clients)
    # the async buffered update (device-buffer insert → maturity gate →
    # staleness-discounted psum mean) — same builder fed_train
    # --mode async --mesh runs, lowered here at paper scale
    buf, up, round_idx, prev, _ = fed_train.abstract_async_inputs(
        tm_cfg, fed_cfg, mesh, capacity=buffer_capacity,
        j_slots=strategy.j_slots)
    async_update = build_sharded_async_update(
        strategy, mesh, axis_name=client_axes, collective="psum",
        min_uploads=4, n_valid=n_clients * strategy.j_slots)

    out = {"mesh": "2x16x16" if multi_pod else "16x16",
           "n_clients": n_clients, "clauses": clauses,
           "buffer_capacity": buffer_capacity}
    with jax.set_mesh(mesh):
        for name, build, args in (
            ("tpfl", engine_round, (params, cw, data, keys, arrive)),
            ("tpfl_async", async_update, (buf, up, round_idx, prev)),
            ("fedavg_tm", fed_train.make_fedavg_tm_round(tm_cfg, fed_cfg),
             (params, data, key)),
        ):
            t0 = time.time()
            compiled = jax.jit(build).lower(*args).compile()
            coll = hlo_analysis.collective_bytes(compiled.as_text())
            total = sum(coll.values())
            out[name] = {
                "collective_bytes_per_device": total,
                "collective_s": total / peaks(V5E).ici_bw_per_link,
                "breakdown": coll,
                "compile_s": round(time.time() - t0, 1),
            }
            print(f"{name:10s}: {total/1e6:.3f} MB/device collectives "
                  f"({out[name]['compile_s']}s compile)", flush=True)

    if out["tpfl"]["collective_bytes_per_device"]:
        out["fedavg_over_tpfl"] = (
            out["fedavg_tm"]["collective_bytes_per_device"]
            / out["tpfl"]["collective_bytes_per_device"])
        print(f"FedAvg-TM moves {out['fedavg_over_tpfl']:.1f}× the "
              f"collective bytes of TPFL")
    out["client_scale"] = client_scale()
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    (ARTIFACTS / f"fed_dryrun_{out['mesh']}.json").write_text(
        json.dumps(out, indent=2))
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--clients", type=int, default=256)
    ap.add_argument("--buffer-capacity", type=int, default=512)
    args = ap.parse_args()
    run(multi_pod=args.multi_pod, n_clients=args.clients,
        buffer_capacity=args.buffer_capacity)
