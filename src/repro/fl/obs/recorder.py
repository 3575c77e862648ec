"""The run recorder: one object the engine talks telemetry through.

The engine holds a single ``telemetry`` object and calls four hooks —
``span(name)`` / ``fence(values)`` around each round stage,
``on_round(report)`` after each round, and reads ``manifest`` when it
checkpoints.  :data:`NULL` (telemetry off, the default) answers all of
them as no-ops, so an un-instrumented engine is byte-for-byte the
pre-telemetry engine; :class:`RunRecorder` (telemetry on) times the
spans, derives the round event, and appends it to the run directory:

    run-dir/
      manifest.json    config, seed, mesh, git sha, jax version
      events.jsonl     one structured event per round

A recorder without a run directory (``RunRecorder()``) records
in-memory only — ``benchmarks/run.py emit_bench`` uses that form to get
the per-phase breakdown without a run dir.

Neutrality: the recorder only ever consumes round *outputs* (the
report) and host wall clocks.  Nothing it computes flows back into the
engine, which is what lets the conformance suite pin obs-on == obs-off
bit for bit.
"""
from __future__ import annotations

import pathlib

import numpy as np

from repro.fl.obs import events as ev
from repro.fl.obs import manifest as mf
from repro.fl.obs.tracer import NullTracer, PhaseTracer, profile_trace


class NullTelemetry(NullTracer):
    """Telemetry disabled: every hook a no-op, shared singleton."""

    manifest = None

    def on_round(self, report) -> None:
        pass

    def close(self) -> None:
        pass


NULL = NullTelemetry()


class RunRecorder(PhaseTracer):
    """Telemetry enabled: spans + structured events (+ optional
    ``jax.profiler`` capture via :func:`start`'s ``profile_dir``)."""

    def __init__(self, run_dir: str | pathlib.Path | None = None,
                 profile_dir: str | pathlib.Path | None = None,
                 fence: bool = True):
        super().__init__(fence=fence)
        self.run_dir = pathlib.Path(run_dir) if run_dir else None
        self.events_path = (self.run_dir / mf.EVENTS_NAME
                            if self.run_dir else None)
        self.profile_dir = profile_dir
        self.manifest: dict | None = None
        self.history: list[dict] = []      # jsonable events, in order
        self._prev_assignment = None
        self._profile_ctx = None

    # -- lifecycle ---------------------------------------------------------

    def start(self, manifest: dict | None = None) -> "RunRecorder":
        """Write the manifest (if a run dir is set) and start the
        profiler capture (if a profile dir is set).  Idempotent per
        recorder; call before the first round."""
        self.manifest = manifest
        if self.run_dir is not None:
            self.run_dir.mkdir(parents=True, exist_ok=True)
            if manifest is not None:
                mf.write_manifest(self.run_dir, manifest)
        if self.profile_dir is not None and self._profile_ctx is None:
            self._profile_ctx = profile_trace(self.profile_dir)
            self._profile_ctx.__enter__()
        return self

    def close(self) -> None:
        """Stop the profiler capture and the compile listener (events
        are flushed per round)."""
        super().close()
        if self._profile_ctx is not None:
            ctx, self._profile_ctx = self._profile_ctx, None
            ctx.__exit__(None, None, None)

    # -- per-round hook ----------------------------------------------------

    def on_round(self, report) -> dict:
        """Derive this round's event from the report + the spans
        accumulated since the last call, and append it to the log."""
        spans = self.take()
        event = ev.round_event(report, spans=spans,
                               compiles=spans.compiles,
                               compile_s=spans.compile_s,
                               prev_assignment=self._prev_assignment)
        self._prev_assignment = np.array(report.assignment)
        if self.events_path is not None:
            event = ev.append_event(self.events_path, event)
        else:
            event = ev.to_jsonable(event)
        self.history.append(event)
        return event
