"""Structured round events: every ``RoundReport`` plus derived gauges,
one JSON object per line.

The event schema (see ``docs/observability.md`` for the field-by-field
contract) is built *from* the report — the obs layer never reaches into
the engine's math, it only derives host-side gauges from what the round
already returned:

* ``accuracy``      — mean, per-decile quantiles of the per-client
  accuracy vector, and the worst-decile mean (the honest pFL metric:
  how the bottom 10 % of clients fare, not just the average).
* ``cluster``       — per-slot contributor counts, slot occupancy and
  per-slot accuracy distribution derived from the confidence-argmax
  assignment (the paper's per-class-confidence dynamic, observed), the
  empty-slot retention rate (fraction of slots Alg. 2 left untouched),
  and assignment churn vs. the previous round (the cluster-identity
  dynamic IFCA-style methods hinge on).
* ``scheduler``     — sampled / dropped / straggler counts and the
  staleness histogram (``Participation.summary()``).
* ``bytes``         — codec-metered wire traffic by direction.
* ``async``         — aggregated / still-buffered / evicted uploads.
* ``store``         — host-I/O bytes read/written by the mmap client
  store this round (0 on the resident engine).
* ``transport``     — framed bytes the real transport (loopback /
  socket, ``repro.fl.transport``) put on and took off the wire this
  round (headers and envelopes included, unlike the codec-metered
  ``bytes`` section), plus the observed-arrival staleness summary of
  the uploads that actually landed (async transport; ``None`` on the
  in-process engine, where staleness is an injected schedule).
* ``phases``        — the round's phase-span wall times (tracer),
  including the ``wire_tx`` / ``wire_rx`` transport spans.
* ``compiles`` / ``compile_s`` — backend compiles and compile seconds
  (trace + lowering + backend compile) the tracer charged to each span
  (``"(none)"``: outside any span) — which stage recompiled.

Serialization is numpy-safe by construction: :func:`to_jsonable`
coerces numpy/jax scalars and arrays (int64 included — ``json`` alone
raises on ``np.int64``) before anything touches the wire, and
:func:`read_events` round-trips the file back to plain Python values.
"""
from __future__ import annotations

import json
import pathlib
from typing import Any

import numpy as np

SCHEMA_VERSION = 1

# decile grid: 0 % (worst client) through 100 % (best), step 10
_DECILES = np.linspace(0.0, 1.0, 11)


def to_jsonable(value: Any) -> Any:
    """Recursively coerce a value into plain JSON types.

    Handles numpy/jax scalars (``np.int64``, ``np.float32``, bools) and
    arrays (→ nested lists), paths, and NaN/inf floats (→ None, since
    JSON has no spelling for them and downstream consumers shouldn't
    have to guess a dialect)."""
    if isinstance(value, dict):
        return {str(k): to_jsonable(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return [to_jsonable(v) for v in value]
    if isinstance(value, pathlib.Path):
        return str(value)
    if isinstance(value, (bool, np.bool_)):
        return bool(value)
    if isinstance(value, (int, np.integer)):
        return int(value)
    if isinstance(value, (float, np.floating)):
        f = float(value)
        return f if np.isfinite(f) else None
    if hasattr(value, "__array__"):          # numpy / jax arrays
        arr = np.asarray(value)
        if arr.ndim == 0:
            return to_jsonable(arr.item())
        return [to_jsonable(v) for v in arr.tolist()]
    return value


def accuracy_deciles(per_client_accuracy) -> list[float]:
    """The 11 decile quantiles (0 %=worst client … 100 %=best) of the
    per-client accuracy vector — the distributional report ROADMAP
    item 5 calls the honest pFL metric."""
    acc = np.asarray(per_client_accuracy, np.float64).ravel()
    return [float(q) for q in np.quantile(acc, _DECILES)]


def worst_decile_mean(per_client_accuracy) -> float:
    """Mean accuracy of the worst 10 % of clients (at least one)."""
    acc = np.sort(np.asarray(per_client_accuracy, np.float64).ravel())
    k = max(1, int(np.ceil(acc.size / 10)))
    return float(acc[:k].mean())


def _cluster_gauges(report, prev_assignment) -> dict:
    counts = np.asarray(report.cluster_counts, np.float64)
    assignment = np.asarray(report.assignment)
    acc = np.asarray(report.per_client_accuracy, np.float64)
    n_slots = counts.shape[0]
    # slot occupancy + per-slot accuracy from the (n, j) assignment:
    # a client "occupies" every slot it shares into (−1 = none)
    occupancy = np.zeros(n_slots, np.int64)
    slot_acc_sum = np.zeros(n_slots, np.float64)
    for j in range(assignment.shape[1] if assignment.ndim == 2 else 0):
        col = assignment[:, j]
        shared = col >= 0
        np.add.at(occupancy, col[shared], 1)
        np.add.at(slot_acc_sum, col[shared], acc[shared])
    slot_accuracy = [
        float(slot_acc_sum[s] / occupancy[s]) if occupancy[s] else None
        for s in range(n_slots)]
    churn = None
    if prev_assignment is not None:
        prev = np.asarray(prev_assignment)
        if prev.shape == assignment.shape:
            churn = float((prev != assignment).any(axis=-1).mean())
    return {
        "counts": counts.tolist(),
        "populated_slots": int((counts > 0).sum()),
        "empty_slot_retention_rate": float((counts == 0).mean()),
        "occupancy": occupancy.tolist(),
        "slot_accuracy": slot_accuracy,
        "churn_vs_prev": churn,
    }


def round_event(report, spans: dict | None = None,
                prev_assignment=None, compiles: dict | None = None,
                compile_s: dict | None = None) -> dict:
    """Build one structured event from a ``RoundReport`` (duck-typed —
    the obs layer has no import edge into the runtime).  ``compiles`` /
    ``compile_s`` are the backend compiles and compile seconds the
    tracer charged to each span.  Pure derivation: nothing here feeds
    back into the round."""
    part = report.participation
    ev = {
        "schema": SCHEMA_VERSION,
        "round": int(report.round_idx),
        "accuracy": {
            "mean": float(report.mean_accuracy),
            "deciles": accuracy_deciles(report.per_client_accuracy),
            "worst_decile_mean": worst_decile_mean(
                report.per_client_accuracy),
        },
        "cluster": _cluster_gauges(report, prev_assignment),
        "scheduler": (part.summary() if hasattr(part, "summary")
                      else None),
        "bytes": {
            "upload": int(report.upload_bytes),
            "download_broadcast": int(report.download_bytes_broadcast),
            "download_per_client": int(report.download_bytes_per_client),
        },
        "async": {
            "aggregated": int(report.aggregated_uploads),
            "buffered": int(report.buffered_uploads),
            "evicted": int(report.evicted_uploads),
        },
        # host-I/O gauges of the mmap client store (0 when resident —
        # getattr keeps older/minimal report shapes valid)
        "store": {
            "read_bytes": int(getattr(report, "store_read_bytes", 0)),
            "written_bytes": int(getattr(report, "store_written_bytes", 0)),
        },
        "transport": _transport_gauges(report),
        "phases": dict(spans) if spans else None,
        "compiles": dict(compiles) if compiles is not None else None,
        "compile_s": dict(compile_s) if compile_s is not None else None,
    }
    return ev


def _transport_gauges(report) -> dict | None:
    """Per-direction framed-byte gauges + observed-arrival staleness of
    the real transport; ``None`` when nothing crossed a process wire
    (the in-process engine)."""
    tx = int(getattr(report, "wire_tx_bytes", 0))
    rx = int(getattr(report, "wire_rx_bytes", 0))
    observed = getattr(report, "observed_staleness", None)
    if tx == 0 and rx == 0 and observed is None:
        return None
    gauges = {"wire_tx_bytes": tx, "wire_rx_bytes": rx}
    if observed is not None:
        # the runner hands either the raw arrival-lag array or the
        # already-derived Participation.summary() dict
        if isinstance(observed, dict):
            gauges["observed"] = observed
        else:
            lags = np.asarray(observed, np.int64).ravel()
            hist = (np.bincount(lags) if lags.size
                    else np.zeros(1, np.int64))
            gauges["observed"] = {
                "arrived": int(lags.size),
                "arrived_on_time": int((lags == 0).sum()),
                "stragglers": int((lags > 0).sum()),
                "staleness_hist": hist.tolist(),
            }
    return gauges


def append_event(path: str | pathlib.Path, event: dict) -> dict:
    """Append one event as a JSONL line (numpy-safe) and return the
    jsonable form that was written."""
    jsonable = to_jsonable(event)
    line = json.dumps(jsonable, sort_keys=True)
    with open(path, "a") as f:
        f.write(line + "\n")
    return jsonable


def read_events(path: str | pathlib.Path) -> list[dict]:
    """Load a run's ``events.jsonl`` back into a list of dicts."""
    events = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                events.append(json.loads(line))
    return events
