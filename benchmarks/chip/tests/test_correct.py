"""What decides ``correct``, rehearsed on the CPU at a small size: a run
of each cell with the program as it is comes out correct; the control
(the plain reference in the precision below the configuration's, put in
the program's place) and each fault planted under the timed path come
out not correct.

    PYTHONPATH=src python -m pytest -q benchmarks/chip/tests

The chip's own readings at the cells' sizes, from which the limits are
set, come from ``calibrate.py``; these tests keep the comparison honest
at a size a test run can hold.
"""
from __future__ import annotations

import io
import json
import pathlib
import sys
from contextlib import redirect_stderr, redirect_stdout

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))

import calibrate  # noqa: E402
import run  # noqa: E402

TM = dict(dataset="synthmnist", n_features=144, n_clauses=8, population=6,
          clients_per_round=3, local_epochs=2, pool_samples=600, n_train=16,
          n_test=8, n_conf=8)
MLP = dict(dataset="synthmnist", n_features=144, n_hidden=16, population=6,
           clients_per_round=4, local_epochs=2, pool_samples=600, n_train=16,
           n_test=8, n_conf=8, batch=8)
ROUND = {"trace_seconds": 0.2}
SERVE = dict(rate_rps=60, max_batch=4, check_sample=24, trace_seconds=0.5,
             span_seconds=0.5)
# the MLP control's mechanism at a size a test holds: an SGD step small
# against the weights, so that bfloat16 weights lose it
MLP_CONTROL = dict(MLP, lr=0.002)
CELLS = {"tpfl-mnist.round": (TM, ROUND),
         "fedavg-mlp-mnist.round": (MLP, ROUND),
         "tpfl-mnist.serve-zipf": (TM, SERVE)}
# The serving cell is not in BENCHMARK.json yet: its tail did not hold
# steady on the chip (PERF.md, Open questions).  Its harness is kept and
# exercised here under the entries a later benchmark change would add.
SERVING = {
    "workloads": [{"name": "tpfl-mnist.serve-zipf", "config": "tpfl-mnist",
                   "traffic": "serve-zipf", "chips": 1,
                   "why": "open-loop personalized serving"}],
    "end_to_end": [
        {"name": "serve_p95_ms", "unit": "ms", "better": "lower",
         "bound": 0.25, "source": "host_clock",
         "workloads": ["tpfl-mnist.serve-zipf"]},
        {"name": "serve_rps", "unit": "requests/s", "better": "higher",
         "bound": 0.01, "source": "host_clock",
         "workloads": ["tpfl-mnist.serve-zipf"]}],
    "per_layer": [
        {"name": n, "unit": u, "better": b, "source": src, "layer": layer,
         "moves": "serve_p95_ms", "workloads": ["tpfl-mnist.serve-zipf"]}
        for n, u, b, src, layer in (
            ("tm_fused_votes_batched_roofline", "%", "higher",
             "device_trace", "kernels"),
            ("device.idle.serve", "%", "lower", "device_trace", "device"),
            ("mfu.serve", "%", "higher", "device_trace", "device"),
            ("serve.resolve_ms", "ms", "lower", "program_span",
             "serving plane"),
            ("gen.lag_ms", "ms", "lower", "host_clock", "load generator"))]}


@pytest.fixture(autouse=True)
def _no_compile_cache():
    import jax
    jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture(autouse=True)
def _with_serving(monkeypatch):
    import harness
    orig = harness.load_benchmark

    def load(root=harness.ROOT):
        bench = orig(root)
        for key, extra in SERVING.items():
            bench[key] = bench[key] + extra
        return bench

    monkeypatch.setattr(harness, "load_benchmark", load)


def _cell(name: str, trace: int = 0, seed: int = 3) -> dict:
    cfg, tr = CELLS[name]
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        rc = run.main(["--workload", name, "--seed", str(seed),
                       "--seconds", "0.5", "--trace", str(trace)],
                      require_tpu=False, config_overrides=cfg,
                      traffic_overrides=tr)
    assert rc == 0, err.getvalue()
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    tail = err.getvalue().strip().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("check ") for t in tail)
    return line


@pytest.mark.parametrize("name", sorted(CELLS))
def test_sound_run_is_correct(name):
    line = _cell(name)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) >= {"setup_s"}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_traced_run_is_correct_and_reports_per_layer_metrics(name):
    line = _cell(name, trace=1)
    assert line["correct"] is True, line["checks"]
    # the CPU has no TPU plane: device metrics stay silent, spans speak
    assert "setup_s" not in line["metrics"]
    assert not any(k.endswith("_roofline") or k.startswith("mfu")
                   or k.startswith("device.") for k in line["metrics"])


def test_fault_state_unchanged_is_not_correct(monkeypatch):
    from repro.fl.runtime import engine

    def unchanged(self, state, round_key):
        new, rep = orig(self, state, round_key)
        return new._replace(client_state=state.client_state,
                            server=state.server), rep

    orig = engine.Engine.run_round
    monkeypatch.setattr(engine.Engine, "run_round", unchanged)
    for name in ("tpfl-mnist.round", "fedavg-mlp-mnist.round"):
        assert _cell(name)["correct"] is False, name


def test_fault_half_of_the_cohort_left_out_is_not_correct(monkeypatch):
    from repro.fl.runtime import executors
    orig = executors.InProcessExecutor.masked_mean

    def half(self, strategy, dec, slots, arrive):
        keep = np.arange(arrive.shape[0]) < arrive.shape[0] // 2
        return orig(self, strategy, dec, slots, arrive & keep)

    monkeypatch.setattr(executors.InProcessExecutor, "masked_mean", half)
    assert _cell("fedavg-mlp-mnist.round")["correct"] is False


def test_fault_half_of_each_client_batch_left_out_is_not_correct(
        monkeypatch):
    from repro.core import tm
    orig = tm.train_batched

    def half(params, xs, ys, keys, cfg, epochs=1):
        n = xs.shape[1] // 2
        return orig(params, xs[:, :n], ys[:, :n], keys, cfg, epochs)

    monkeypatch.setattr(tm, "train_batched", half)
    assert _cell("tpfl-mnist.round")["correct"] is False


def test_fault_altered_answer_is_not_correct(monkeypatch):
    from repro.fl.serve import plane
    orig = plane.ServingPlane.predict

    def altered(self, client_ids, x):
        out = orig(self, client_ids, x).copy()
        out[0] = (out[0] + 1) % 10
        return out

    monkeypatch.setattr(plane.ServingPlane, "predict", altered)
    assert _cell("tpfl-mnist.serve-zipf")["correct"] is False


@pytest.mark.parametrize("name", ["tpfl-mnist.round",
                                  "fedavg-mlp-mnist.round"])
def test_round_control_fails_a_limit(name):
    cfg, tr = CELLS[name]
    if name.startswith("fedavg"):
        cfg = MLP_CONTROL
    got = calibrate.round_seed(name, 5, True, None, (cfg, tr))
    limits = json.loads((BENCH / "configs" / (
        "tpfl-mnist.json" if name.startswith("tpfl") else
        "fedavg-mlp-mnist.json")).read_text())["limits"]["round"]
    assert all(got["program"][k] <= v for k, v in limits.items())
    assert any(got["control"][k] > v for k, v in limits.items())
    assert any(got["unchanged"][k] > v for k, v in limits.items())


def test_serve_control_fails_the_limit():
    got = calibrate.serve_seed("tpfl-mnist.serve-zipf", 5, True, 1.0,
                               CELLS["tpfl-mnist.serve-zipf"])
    assert got["program"]["mismatches"] == 0
    assert got["control_other_client"]["mismatches"] > 0
    assert "mismatches" in got["control_bf16"]
