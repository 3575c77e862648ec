"""Federated telemetry plane (``repro.fl.obs``).

Three layers, none of which may perturb the round's numerics (obs-on ==
obs-off bit for bit, pinned by the conformance suite):

* **phase-span tracing** (:mod:`~repro.fl.obs.tracer`) — host wall time
  per round stage (fenced with ``jax.block_until_ready`` or not), each
  stage an ``engine.<stage>`` annotation on the profiler's timeline
  and charged the compiles it caused, plus optional ``jax.profiler``
  capture;
* **structured round events** (:mod:`~repro.fl.obs.events` /
  :mod:`~repro.fl.obs.manifest` / :mod:`~repro.fl.obs.recorder`) —
  per-round JSONL (accuracy deciles, cluster churn and occupancy,
  empty-slot retention, staleness histograms, wire bytes, phase times)
  next to a run manifest (config, seed, mesh, git sha, jax version);
* **a consumer** (:mod:`~repro.fl.obs.summarize`) —
  ``python -m repro.fl.obs summarize <run-dir>``.

Deliberately import-light: the obs package duck-types on the runtime's
``RoundReport`` instead of importing it, so the runtime can depend on
obs (``Engine(telemetry=...)``) without a cycle.  See
``docs/observability.md``.
"""
from repro.fl.obs.events import (SCHEMA_VERSION, accuracy_deciles,
                                 append_event, read_events, round_event,
                                 to_jsonable, worst_decile_mean)
from repro.fl.obs.manifest import (build_manifest, git_sha, read_manifest,
                                   write_manifest)
from repro.fl.obs.recorder import NULL, NullTelemetry, RunRecorder
from repro.fl.obs.summarize import (phase_compiles, phase_medians,
                                    summarize)
from repro.fl.obs.tracer import (NullTracer, PhaseTracer, Spans,
                                 profile_trace)

__all__ = [
    "SCHEMA_VERSION", "accuracy_deciles", "append_event", "read_events",
    "round_event", "to_jsonable", "worst_decile_mean",
    "build_manifest", "git_sha", "read_manifest", "write_manifest",
    "NULL", "NullTelemetry", "RunRecorder",
    "phase_compiles", "phase_medians", "summarize",
    "NullTracer", "PhaseTracer", "Spans", "profile_trace",
]
