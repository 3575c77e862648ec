"""The benchmark's own arithmetic on the CPU: trace reduction, window
statistics, operation counts, discovery by name, the contract's shape
of ``BENCHMARK.json``, and the refusal to report without a TPU.

    PYTHONPATH=src python -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

import json
import math
import os
import pathlib
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

import counts  # noqa: E402
import harness  # noqa: E402
import peaks  # noqa: E402
import trace_reduce  # noqa: E402

TESTDATA = BENCH / "testdata"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


# -- trace reduction ---------------------------------------------------------

def _small_trace() -> dict:
    return json.loads((TESTDATA / "trace_small.json").read_text())


def _window_of(ev):
    ann = [(s, s + d) for n, s, d, _ in ev["host"] if n == ev["annotation"]]
    return min(a for a, _ in ann), max(b for _, b in ann)


def test_trace_busy_is_the_union_of_device_ops_inside_the_window():
    ev = _small_trace()
    red = trace_reduce.reduce(ev, ev["annotation"], ev["kernels"])
    lo, hi = _window_of(ev)
    assert red["window_s"] == pytest.approx((hi - lo) / 1e9)
    # an endpoint sweep with a depth counter, independent of the interval
    # merge under test
    edges = []
    for _, s, d in ev["device"][0]["ops"]:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            edges += [(a, 1), (b, -1)]
    depth, last, covered = 0, None, 0
    for t, step in sorted(edges, key=lambda x: (x[0], -x[1])):
        if depth > 0:
            covered += t - last
        depth, last = depth + step, t
    assert red["busy_s"] == pytest.approx(covered / 1e9)
    assert 0.0 < red["busy_s"] < red["window_s"]


def test_trace_kernel_time_is_its_ops_own_time():
    ev = _small_trace()
    red = trace_reduce.reduce(ev, ev["annotation"], ev["kernels"])
    lo, hi = _window_of(ev)
    for k in ev["kernels"]:
        # kernels are leaves: their own time is their duration
        want = sum(min(s + d, hi) - max(s, lo)
                   for n, s, d in ev["device"][0]["ops"]
                   if n.startswith(f"%{k}.") or n.startswith(f"%{k} ")
                   if min(s + d, hi) > max(s, lo))
        assert want > 0
        assert red["kernel_s"][k] == pytest.approx(want / 1e9)
    # nested ops are charged once: kernels + outside = busy
    assert red["outside_s"] + sum(red["kernel_s"].values()) == \
        pytest.approx(red["busy_s"], rel=1e-3)


def test_nested_ops_are_charged_their_own_time():
    ev = {"host": [["w", 0, 100, "main"]],
          "device": [{"plane": "/device:TPU:0", "ops": [
              ["%while.1 = (s32[]) while(...)", 10, 60],
              ["%k.2 = s32[] custom-call(%while.1)", 20, 15],
              ["%fusion.3 = s32[] fusion(%k.2)", 40, 10]]}]}
    red = trace_reduce.reduce(ev, "w", ["k"])
    assert red["busy_s"] == pytest.approx(60e-9)
    assert red["kernel_s"]["k"] == pytest.approx(15e-9)
    assert red["outside_s"] == pytest.approx(45e-9)
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["%while.1 = (s32[]) while(...)"] == pytest.approx(35e-9)
    assert dict(red["breakdown"]["idle_gaps"])["w"] == pytest.approx(40e-9)


def test_trace_breakdown_lists_top_ops_and_idle_gaps():
    ev = _small_trace()
    red = trace_reduce.reduce(ev, ev["annotation"], ev["kernels"])
    ops, gaps = red["breakdown"]["device_ops"], red["breakdown"]["idle_gaps"]
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert [v for _, v in ops] == sorted((v for _, v in ops), reverse=True)
    assert sum(v for _, v in gaps) == pytest.approx(
        red["window_s"] - red["busy_s"])
    assert all(isinstance(n, str) and v > 0 for n, v in ops + gaps)


def test_trace_without_the_annotation_is_refused():
    ev = _small_trace()
    with pytest.raises(ValueError, match="no host event"):
        trace_reduce.reduce(ev, "not-there", ev["kernels"])


# -- window arithmetic -------------------------------------------------------

def _window(due, done, t_start=100.0):
    due = t_start + np.asarray(due, float)
    return {"t_start": t_start, "due": due, "enq": due + 0.001,
            "done": t_start + np.asarray(done, float)}


def test_rate_is_answered_requests_over_the_whole_window():
    serve = harness.load_module("drivers", "serve")
    due = np.arange(100) * 0.1                 # 10 requests/s for 10 s
    got = serve.window_metrics(_window(due, due + 0.02), 10.0)
    assert got["serve_rps"] == pytest.approx(10.0)
    assert got["serve_p95_ms"] == pytest.approx(20.0)
    assert got["failed"] == 0


def test_tail_is_over_all_requests_and_a_stall_moves_it():
    serve = harness.load_module("drivers", "serve")
    due = np.arange(100) * 0.1
    done = due + 0.02
    # a 2 s stall at t = 5 s: every request due in it waits for its end
    stalled = np.where((due >= 5.0) & (due < 7.0), 7.0 + 0.02, done)
    base = serve.window_metrics(_window(due, done), 10.0)
    hit = serve.window_metrics(_window(due, stalled), 10.0)
    lat = np.sort(stalled - due)
    assert hit["serve_p95_ms"] == pytest.approx(
        lat[math.ceil(0.95 * lat.size) - 1] * 1e3)
    assert hit["serve_p95_ms"] > 10 * base["serve_p95_ms"]
    # answered late but inside the window: the rate holds
    assert hit["serve_rps"] == pytest.approx(base["serve_rps"])


def test_unanswered_request_is_infinitely_late_and_failed():
    serve = harness.load_module("drivers", "serve")
    due = np.arange(10) * 0.1
    done = due + 0.01
    done[3:] = np.nan
    got = serve.window_metrics(_window(due, done), 1.0)
    assert got["failed"] == 7 and math.isinf(got["serve_p95_ms"])
    assert got["serve_rps"] == pytest.approx(3.0)


def test_schedule_offers_every_seed_the_same_gaps_and_clients_counts():
    serve = harness.load_module("drivers", "serve")
    tr = {"rate_rps": 200, "zipf_exponent": 1.1}
    a = serve.schedule(1, tr, 5.0, 100, 40)
    b = serve.schedule(2 ** 33 + 1, tr, 5.0, 100, 40)
    assert a["due"].size == b["due"].size == 1000
    gaps = lambda s: np.sort(np.diff(np.concatenate([[0.0], s["due"]])))
    assert np.allclose(gaps(a), gaps(b))
    assert a["due"][-1] == pytest.approx(5.0, rel=0.05)
    assert sorted(np.bincount(a["client"], minlength=100)) == \
        sorted(np.bincount(b["client"], minlength=100))
    assert not np.array_equal(a["client"], b["client"])
    again = serve.schedule(1, tr, 5.0, 100, 40)
    assert all(np.array_equal(a[k], again[k]) for k in a)


def test_seed_keeps_both_words():
    assert not np.array_equal(harness.key_data(5), harness.key_data(2 ** 32 + 5))
    assert list(harness.key_data(2 ** 31 + 5)) == [0, 2 ** 31 + 5]


# -- operation counts --------------------------------------------------------

def test_tm_counts_by_hand():
    # C=2 classes, m=4 clauses, o=3 features (L=6), 2 clients, 3 epochs,
    # 5 samples: a sample-step is 2 roles x (24 checks + 24 transitions)
    assert counts.tm_train_ops(2, 3, 5, 4, 6) == 2 * 3 * 5 * 96
    assert counts.tm_train_bytes(2, 3, 5, 2, 4, 6) == 2 * 3 * (96 + 3)
    assert counts.tm_predict_ops(7, 2, 4, 6) == 7 * 48
    assert counts.tm_predict_bytes(3, 2, 4, 6) == 3 * (6 + 32)
    assert counts.tm_round_ops(2, 10, 3, 5, 4, 8, 2, 4, 6) == \
        2 * 3 * 5 * 96 + 2 * 4 * 48 + 10 * 8 * 48


def test_mlp_counts_by_hand():
    assert counts.mlp_params(4, 3, 2) == 4 * 3 + 3 + 3 * 2 + 2
    # 80 samples in batches of 32: two whole batches a client-epoch
    p = 23
    assert counts.mlp_round_flops(2, 5, 3, 80, 32, 4, 4, 3, 2) == \
        2 * 3 * 2 * 32 * 6 * p + 5 * 4 * 2 * p


def test_least_time_is_the_longer_of_compute_and_memory():
    assert counts.least_seconds(393e12, 1.0, 393e12, 819e9) == \
        pytest.approx(1.0)
    assert counts.least_seconds(1.0, 2 * 819e9, 393e12, 819e9) == \
        pytest.approx(2.0)


def test_peaks_table_has_the_published_v5e_rates_and_refuses_unknowns():
    p = peaks.peaks("TPU v5 lite")
    assert (p.bf16_flops, p.int8_ops, p.hbm_bw) == (197e12, 393e12, 819e9)
    assert p.rate("int8_ops") == 393e12
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("cpu")


# -- discovery and the file's shape ------------------------------------------

def test_every_cell_finds_its_files_by_name():
    bench = harness.load_benchmark()
    for cell in bench["workloads"]:
        cfg = harness.load_config(cell["config"])
        tr = harness.load_traffic(cell["traffic"])
        assert callable(harness.load_module("drivers", tr["driver"]).run)
        harness.load_module("reference", cfg["reference"])
        metrics = harness.per_layer_for(bench, cell["name"])
        assert metrics, cell["name"]
        for m in metrics:
            reader = harness.load_module("metrics", m["name"])
            assert reader.read({}) is None
        assert {m["name"] for m in harness.end_to_end_for(
            bench, cell["name"])} >= {"setup_s"}


def test_every_file_of_the_harness_loads_by_its_name():
    # traffic mixes and metric readers kept for cells a later change adds
    for path in sorted((BENCH / "traffic").glob("*.json")):
        tr = harness.load_traffic(path.stem)
        assert callable(harness.load_module("drivers", tr["driver"]).run)
    for path in sorted((BENCH / "metrics").glob("*.py")):
        assert harness.load_module("metrics", path.stem).read({}) is None
    for path in sorted((BENCH / "configs").glob("*.json")):
        cfg = harness.load_config(path.stem)
        harness.load_module("reference", cfg["reference"])


def test_benchmark_file_keeps_the_contract_shape():
    bench = harness.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert (ROOT / bench["command"][1]).is_file()
    assert all((ROOT / p).is_dir() for p in bench["paths"])
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for cfg in bench["configs"]:
        assert set(cfg) == {"name", "source", "file", "reduced", "why"}
        data = json.loads((ROOT / cfg["file"]).read_text())
        assert all(k in data for k in cfg["reduced"])
        assert data["reduced"] == cfg["reduced"]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        for cell in m["workloads"]:
            assert m["moves"] in {x["name"] for x in
                                  harness.end_to_end_for(bench, cell)}
        assert m["moves"] in e2e
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for cell in bench["workloads"]:
        assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200


# -- no result without the chip ----------------------------------------------

def _run(cwd: pathlib.Path, *args) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cwd / ".no_cache"))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120)


def test_run_refuses_without_a_tpu():
    res = _run(ROOT, "--workload", "tpfl-mnist.round", "--seed", "1",
               "--seconds", "1", "--trace", "0")
    assert res.returncode != 0 and res.stdout.strip() == ""
    assert "not 'tpu'" in res.stderr


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("_work", "__pycache__"))
    res = _run(tmp_path, "--workload", "tpfl-mnist.round", "--seed", "1",
               "--seconds", "1", "--trace", "0")
    assert res.returncode != 0 and res.stdout.strip() == ""
    assert "not in this checkout" in res.stderr
