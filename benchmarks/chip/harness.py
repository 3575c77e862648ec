"""Shared plumbing of the chip benchmark: ``BENCHMARK.json``, discovery
of configurations, traffic mixes, drivers and metric readers by name,
the seed, the checks that decide ``correct``, and the result line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own under this directory, found
by the name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json``  — sizes, source, ``reduced``, ``assumed``;
* ``traffic/<traffic>.json`` — the mix's parameters and its ``driver``;
* ``drivers/<driver>.py``    — ``run(ctx) -> Outcome``;
* ``metrics/<metric>.py``    — ``read(records) -> float | None``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
from typing import Any

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]
# scratch space of a run, inside the checkout (listed in .gitignore)
WORK_DIR = BENCH_DIR / "_work"


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _named_file(kind: str, name: str, suffix: str) -> pathlib.Path:
    path = BENCH_DIR / kind / f"{name}{suffix}"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    return path


def load_config(name: str) -> dict:
    return json.loads(_named_file("configs", name, ".json").read_text())


def load_traffic(name: str) -> dict:
    return json.loads(_named_file("traffic", name, ".json").read_text())


def load_module(kind: str, name: str):
    """``drivers/<name>.py`` or ``metrics/<name>.py`` as a module (names
    may hold dots, so they are loaded by path)."""
    path = _named_file(kind, name, ".py")
    mod_name = f"_bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload named {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def end_to_end_for(bench: dict, cell_name: str) -> list[dict]:
    return [m for m in bench["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])]


def per_layer_for(bench: dict, cell_name: str) -> list[dict]:
    """Per-layer metrics this cell reports: those that list it, and
    those without a list whose end-to-end metric the cell reports."""
    e2e = {m["name"] for m in end_to_end_for(bench, cell_name)}
    return [m for m in bench["per_layer"]
            if (cell_name in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def key_data(seed: int):
    """The legacy uint32[2] PRNG key of a 64-bit seed: ``PRNGKey(seed)``
    with both words kept (``PRNGKey`` drops the high word when 64-bit
    mode is off, so seeds 2**32 apart would collide)."""
    import numpy as np
    s = int(seed) & 0xFFFFFFFFFFFFFFFF
    return np.asarray([s >> 32, s & 0xFFFFFFFF], np.uint32)


@dataclasses.dataclass
class Check:
    """One number compared, beside its limit: it passes iff
    ``value <= limit``."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a driver hands back to ``run.py``."""
    attempted: int
    failed: int
    end_to_end: dict[str, float]          # name -> value (trace 0)
    records: dict[str, Any]               # what metric readers read
    checks: list[Check]
    memory_peak_bytes: int
    device_busy_s: float | None = None    # trace 1: from the profiler
    window_s: float | None = None
    breakdown: dict | None = None


@dataclasses.dataclass
class Context:
    """What a driver is given: the cell, its files, the run's flags."""
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t0: float                            # process start (perf_counter)
    work_dir: pathlib.Path
    # seconds of each part of set-up (and the window's compile count),
    # printed to standard error
    setup_parts: dict = dataclasses.field(default_factory=dict)


class CompileCounter:
    """Counts the backend compiles JAX reports while it is entered (a
    window should see none: every shape is warmed in set-up)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __enter__(self) -> "CompileCounter":
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event: str, duration: float, **kwargs) -> None:
        if event == self.EVENT:
            self.n += 1

    def __exit__(self, *exc) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def take_trace(outcome: Outcome, reduced: dict) -> bool:
    """Hand a reduced device trace to the outcome and the metric readers;
    a trace with no device plane (the CPU) measured no device and is
    left out, so no device metric is reported from it."""
    if not reduced["devices"]:
        return False
    outcome.records["trace"] = reduced
    outcome.device_busy_s = reduced["busy_s"]
    outcome.window_s = reduced["window_s"]
    outcome.breakdown = reduced["breakdown"]
    return True


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest local device (0 where the backend
    keeps no statistics)."""
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def result_line(outcome: Outcome, metrics: dict, device: dict) -> str:
    """The last line of standard output; ``checks`` comes last."""
    out = {"correct": all(c.ok for c in outcome.checks)
           and bool(outcome.checks),
           "attempted": outcome.attempted, "failed": outcome.failed,
           "metrics": metrics, "device": device}
    if outcome.breakdown is not None:
        out["breakdown"] = outcome.breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in outcome.checks}
    return json.dumps(out)


def check_lines(checks: list[Check]) -> list[str]:
    return [f"check {c.name} = {c.value!r} (limit {c.limit!r}) "
            f"{'ok' if c.ok else 'FAILED'}" for c in checks]
