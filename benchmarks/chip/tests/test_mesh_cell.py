"""The four-chip cell ``tpfl-mnist-k100.round-4chip`` on the CPU: its two
mesh metrics read by name from a small recorded trace, one chip's share
of the work, its entries in ``BENCHMARK.json``, and a run of the cell at
a small size on four virtual CPU devices that comes out correct while
the control does not.

    PYTHONPATH=src python -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import trace_reduce  # noqa: E402
import trace_scopes  # noqa: E402

CELL = "tpfl-mnist-k100.round-4chip"
SCOPE = "mesh.collective"


def _recorded(name: str) -> dict:
    return json.loads((BENCH / "testdata" / name).read_text())


def test_collective_ms_is_the_scopes_own_time_averaged_over_the_chips():
    ev = _recorded("trace_scopes_mesh.json")
    red = trace_scopes.reduce(ev, ev["annotation"], [SCOPE])
    ann = [(h[1], h[1] + h[2]) for h in ev["host"]
           if h[0] == ev["annotation"]]
    lo, hi = min(a for a, _ in ann), max(b for _, b in ann)
    # the collective's ops sit in idle gaps: their own time is their span
    per_plane = [sum(min(s + d, hi) - max(s, lo) for _, s, d, op in
                     dev["ops"] if trace_scopes.in_scope(op, SCOPE))
                 for dev in ev["device"]]
    assert len(per_plane) == 2 and all(ns > 0 for ns in per_plane)
    assert per_plane[0] != per_plane[1]
    want_ms = sum(per_plane) / len(per_plane) / len(ann) / 1e6
    reader = harness.load_module("metrics", "mesh.collective_ms")
    assert reader.read({"scopes": red}) == pytest.approx(want_ms)


def test_collective_ms_is_silent_where_no_op_carries_the_scope():
    ev = _recorded("trace_scopes_small.json")
    red = trace_scopes.reduce(ev, ev["annotation"], [SCOPE])
    reader = harness.load_module("metrics", "mesh.collective_ms")
    assert reader.read({"scopes": red}) is None
    assert reader.read({}) is None


def test_collective_bytes_reads_the_engines_gauge():
    from repro.fl import masked_collectives
    cfg = harness.load_config("tpfl-mnist-k100")
    gauge = masked_collectives.collective_payload_bytes(
        "gather", cfg["clients_per_round"], cfg["n_clauses"],
        cfg["n_classes"])
    assert gauge == 4 * 100 * 300
    reader = harness.load_module("metrics", "mesh.collective_bytes")
    assert reader.read({"collective_bytes": gauge}) == 120000.0
    assert reader.read({}) is None


def test_work_is_one_chips_share_of_the_round():
    mesh = harness.load_module("drivers", "round_mesh")
    rnd = harness.load_module("drivers", "round")
    cfg = harness.load_config("tpfl-mnist-k100")
    chips = harness.load_traffic("round-4chip")["mesh"]["clients"]
    share = mesh.work_per_chip(cfg, chips)
    assert share == rnd.work_per_round(
        dict(cfg, clients_per_round=25, population=25))
    whole = rnd.work_per_round(cfg)
    assert share["round_ops"] * chips == whole["round_ops"]
    # the device trace's per-plane average reads one chip's kernel time
    ev = {"host": [["w", 0, 100, "main"]],
          "device": [{"plane": f"/device:TPU:{i}", "ops": [
              ["%tm_train_epoch_fused.1 = s32[] custom-call()", 10, 40]]}
              for i in range(chips)]}
    red = trace_reduce.reduce(ev, "w", ["tm_train_epoch_fused"])
    assert red["kernel_s"]["tm_train_epoch_fused"] == pytest.approx(40e-9)


def test_benchmark_holds_the_cell_on_four_chips_unreduced():
    bench = harness.load_benchmark()
    cell = harness.cell(bench, CELL)
    assert cell["chips"] == 4
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) == 1 or len(four) <= len(bench["workloads"]) // 2
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = harness.load_config(cell["config"])
    assert entry["reduced"] == cfg["reduced"] == []
    assert cfg["clients_per_round"] == cfg["population"] == 100
    tr = harness.load_traffic(cell["traffic"])
    assert cfg["population"] % tr["mesh"]["clients"] == 0
    names = {m["name"] for m in harness.per_layer_for(bench, CELL)}
    assert {"mesh.collective_ms", "mesh.collective_bytes", "mfu.train",
            "tm_train_epoch_fused_roofline",
            "engine.compiles_per_round"} <= names
    assert CELL in {w for m in bench["end_to_end"]
                    if m["name"] == "round_s" for w in m["workloads"]}


CHILD = r"""
import io, json, sys
from contextlib import redirect_stderr, redirect_stdout
sys.path.insert(0, sys.argv[1])
sys.path.insert(0, sys.argv[2])
import jax
jax.config.update("jax_enable_compilation_cache", False)
import calibrate_mesh, run

TM = dict(dataset="synthmnist", n_features=144, n_clauses=8, population=8,
          clients_per_round=8, local_epochs=2, pool_samples=600, n_train=16,
          n_test=8, n_conf=8)
lines = {}
for trace in (0, 1):
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        rc = run.main(["--workload", "tpfl-mnist-k100.round-4chip",
                       "--seed", "4100000003", "--seconds", "0.5",
                       "--trace", str(trace)], require_tpu=False,
                      config_overrides=TM,
                      traffic_overrides={"trace_seconds": 0.2})
    assert rc == 0, err.getvalue()
    lines[trace] = json.loads(out.getvalue().strip().splitlines()[-1])
out = io.StringIO()
with redirect_stdout(out):
    cal = calibrate_mesh.round_seed("tpfl-mnist-k100.round-4chip", 5, True,
                                    overrides=(TM, {}))
print(json.dumps({"lines": lines, "calibrate": cal,
                  "devices": len(jax.devices())}))
"""


@pytest.fixture(scope="module")
def small_cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    res = subprocess.run(
        [sys.executable, "-c", CHILD, str(BENCH), str(ROOT / "src")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-4000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_small_cell_on_four_devices_is_correct(small_cell):
    assert small_cell["devices"] == 4
    for trace, line in small_cell["lines"].items():
        assert line["correct"] is True, (trace, line["checks"])
        assert line["device"]["count"] == 4
    assert set(small_cell["lines"]["0"]["metrics"]) == {"setup_s",
                                                        "round_s"}
    traced = small_cell["lines"]["1"]["metrics"]
    # the CPU has no TPU plane: device metrics stay silent, the rest speak
    assert traced["engine.compiles_per_round"]["value"] == 0.0
    assert traced["mesh.collective_bytes"]["value"] == 4 * 8 * 8
    assert traced["wire.bytes_per_round"]["value"] > 0
    assert "mesh.collective_ms" not in traced and "mfu.train" not in traced


def test_small_cell_control_and_unchanged_state_fail_a_limit(small_cell):
    got = small_cell["calibrate"]
    limits = harness.load_config("tpfl-mnist-k100")["limits"]["round"]
    assert all(got["program"][k] <= v for k, v in limits.items())
    assert any(got["control"][k] > v for k, v in limits.items())
    assert any(got["unchanged"][k] > v for k, v in limits.items())
