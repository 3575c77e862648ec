#!/usr/bin/env python3
"""Bring-up smoke test: the federated main path on one TPU chip, at the
paper's MNIST widths, through the entry points a user calls.

    python3 chip_smoke.py [--out DIR]          # one chip
    python3 chip_smoke.py --four-chips         # clients:4 sharded round

One process, no subprocesses: it calls ``fed_train.main`` and
``fed_serve.main`` directly, so the chip is held by this process alone.
Widths: C = 10 classes, m = 300 clauses per class, o = 784 features
(the ``mnist`` mirror is 28×28), so L = 1568 literals; 20 clients,
3 rounds, 2 local epochs.  Weights start from the seed; the data is the
offline mirror, written under ``--out`` on first use.

Phases (one chip):

1. device — JAX's backend must be ``tpu``; no accelerator, no result.
2. train, ``--tm-backend ref`` — last-round accuracy finite and above
   chance, byte totals nonzero; per-round wall times from the telemetry
   spans are printed as smoke timings (round 0 includes compilation).
3. train, ``--tm-backend pallas`` — the round reports (per-client
   accuracy, slot assignments, upload/download bytes) and the final
   engine state must equal phase 2's bit for bit.
4. serve — ``fed_serve`` on phase 3's checkpoint under the Pallas
   backend with ``--verify-offline``: every served prediction equals the
   unbatched offline prediction.

``--four-chips`` runs only the shard-mapped round (``--mesh clients:4
--collective gather``) and the in-process round it must equal bit for
bit.  The shard-mapped engine places its population on the mesh at
``init`` (client state and data five clients a chip, the server state
replicated), and its rounds leave it there.  The last line of standard
output is one JSON object, ``{"ok": true, "device": {...}}``; any
failed phase exits nonzero before it.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import shutil
import sys

HERE = pathlib.Path(__file__).resolve().parent
DEFAULT_OUT = HERE / "chip_smoke_out"

WIDTHS = {"classes": 10, "clauses": 300, "literals": 1568, "clients": 20}
ROUNDS = 3
# what two runs of the same round must share, bit for bit
PARITY_KEYS = ("acc_per_round", "per_client_accuracy", "assignment",
               "upload_bytes", "download_bytes_broadcast",
               "download_bytes_per_client", "state_sha256")


def _fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def _train_argv(out: pathlib.Path) -> list[str]:
    return ["--dataset", "mnist", "--data-dir", str(out / "data"),
            "--clauses", str(WIDTHS["clauses"]),
            "--clients", str(WIDTHS["clients"]),
            "--rounds", str(ROUNDS), "--local-epochs", "2"]


def _round_times(run_dir: pathlib.Path) -> list[float]:
    events = [json.loads(line) for line in
              (run_dir / "events.jsonl").read_text().splitlines() if line]
    return [float(e["phases"]["round"]) for e in events]


def _train(fed_train, out: pathlib.Path, label: str,
           extra: list[str]) -> dict:
    tel = out / "telemetry" / label
    res = fed_train.main(_train_argv(out) + extra
                         + ["--telemetry-dir", str(tel)])
    if res["widths"] != WIDTHS:
        _fail(f"{label}: ran at {res['widths']}, expected {WIDTHS}")
    acc = res["acc_per_round"]
    if len(acc) != ROUNDS or not all(math.isfinite(a) for a in acc):
        _fail(f"{label}: accuracies {acc}")
    if acc[-1] <= 1.0 / WIDTHS["classes"]:
        _fail(f"{label}: last-round accuracy {acc[-1]} is not above chance")
    if min(res["upload_bytes"], res["download_bytes_broadcast"],
           res["download_bytes_per_client"]) <= 0:
        _fail(f"{label}: zero byte totals")
    times = _round_times(tel)
    print(f"smoke timing [{label}] round 0 (includes compilation): "
          f"{times[0]:.3f} s", flush=True)
    print(f"smoke timing [{label}] rounds 1..{ROUNDS - 1}: "
          + " ".join(f"{t:.3f}" for t in times[1:]) + " s", flush=True)
    print(f"{label}: acc_per_round={acc} upload={res['upload_bytes']}B "
          f"download_bc={res['download_bytes_broadcast']}B "
          f"state_sha256={res['state_sha256'][:16]}", flush=True)
    return res


def _same(a: dict, b: dict, what: str) -> None:
    diff = [k for k in PARITY_KEYS if a[k] != b[k]]
    if diff:
        _fail(f"{what}: not bit-equal in {diff}")
    print(f"{what}: bit-equal ({', '.join(PARITY_KEYS)})", flush=True)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=str(DEFAULT_OUT),
                    help="directory for data, checkpoints and telemetry")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the clients:4 shard-mapped round and "
                         "the in-process round it must equal")
    args = ap.parse_args(argv)

    src = HERE / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    from repro.launch import compile_cache
    print(f"compile cache: {compile_cache.configure()}", flush=True)

    import jax

    # -- phase 1: device ---------------------------------------------------
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX backend is {jax.default_backend()!r}, "
              "not 'tpu' — no accelerator, no result", file=sys.stderr)
        return 1
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    print(f"widths: C={WIDTHS['classes']} m={WIDTHS['clauses']} "
          f"o={WIDTHS['literals'] // 2} L={WIDTHS['literals']} "
          f"clients={WIDTHS['clients']} rounds={ROUNDS} local_epochs=2",
          flush=True)

    out = pathlib.Path(args.out)
    for stale in ("ckpt", "telemetry"):
        shutil.rmtree(out / stale, ignore_errors=True)

    from repro.launch import fed_train

    if args.four_chips:
        if len(devices) < 4:
            _fail(f"--four-chips needs 4 devices, found {len(devices)}")
        from repro.launch.mesh import make_clients_mesh
        mesh = make_clients_mesh(4)
        if len(set(mesh.devices.flat)) != 4:
            _fail(f"clients mesh holds {mesh.devices}, not 4 devices")
        print(f"clients mesh: {[d.id for d in mesh.devices.flat]}",
              flush=True)
        ref = _train(fed_train, out, "inprocess", [])
        sharded = _train(fed_train, out, "clients4",
                         ["--mesh", "clients:4", "--collective", "gather"])
        _same(ref, sharded, "clients:4 gather round == in-process round")
    else:
        ckpt = out / "ckpt"
        ref = _train(fed_train, out, "ref",
                     ["--tm-backend", "ref", "--ckpt-dir",
                      str(ckpt / "ref"), "--ckpt-every", "1"])
        pallas = _train(fed_train, out, "pallas",
                        ["--tm-backend", "pallas", "--ckpt-dir",
                         str(ckpt / "pallas"), "--ckpt-every", "1"])
        _same(ref, pallas, "ref == pallas")

        # -- phase 4: serve ------------------------------------------------
        from repro.launch import fed_serve
        served = fed_serve.main(
            ["--dataset", "mnist", "--data-dir", str(out / "data"),
             "--clauses", str(WIDTHS["clauses"]),
             "--clients", str(WIDTHS["clients"]), "--local-epochs", "2",
             "--ckpt-dir", str(ckpt / "pallas"), "--tm-backend", "pallas",
             "--batch", "32", "--requests", "4", "--verify-offline"])
        if served.get("mismatches") != 0 or \
                served.get("verified_clients") != WIDTHS["clients"]:
            _fail(f"serve verify: {served}")
        print(f"serve: {served['requests']} requests, verify-offline "
              f"passed for {served['verified_clients']} clients; smoke "
              f"timing p50={served['p50_s']:.6f} s "
              f"p99={served['p99_s']:.6f} s per batch", flush=True)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
