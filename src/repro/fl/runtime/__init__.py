"""Federated runtime: scheduler, strategies, wire codec, round engine.

The subsystem that replaces the monolithic ``federation.run`` loop:

* :mod:`repro.fl.runtime.scheduler` — K-of-N client sampling (uniform /
  weighted / round-robin) with dropout and straggler-staleness injection.
* :mod:`repro.fl.runtime.strategy` — the ``Strategy`` protocol (v2)
  unifying sync/async TPFL and the FedAvg / FedProx / IFCA / FLIS-DC /
  FLIS-HC / FedTM baselines behind one ``client_step / aggregate /
  broadcast`` surface, with strategy-owned :class:`ServerState` and the
  optional server-side ``assign`` / ``server_update`` hooks (dynamic
  per-round cluster assignment, custom empty-slot retention).
* :mod:`repro.fl.runtime.codec` — quantized (int8/int4) + sparse-delta
  wire encoding of the uploaded vectors, with byte-exact metering
  (``len(buffer)``, not arithmetic).
* :mod:`repro.fl.runtime.engine` — the orchestrated round engine: the
  client half, then the server half (``Engine.serve_uploads``, shared
  with the transport runner) — sync barrier or async buffered
  aggregation (fixed-capacity *device* buffer, masked validity,
  staleness-discounted averaging).
* :mod:`repro.fl.runtime.rows` — where the client rows live, behind one
  ``gather`` / ``commit`` seam: resident device rows (static-K gather /
  scatter of the sampled sub-pytrees) or the mmap client store.
* :mod:`repro.fl.runtime.executors` — where a round's compute runs: the
  in-process vmap backend, or the shard-mapped ``clients``-mesh backend
  whose aggregation — sync masked mean *and* the async buffered update —
  is a single masked collective (bit-identical to in-process; pinned by
  ``tests/test_fl_conformance.py``).
* :mod:`repro.fl.runtime.checkpointing` — round-granular save/resume on
  top of ``repro.checkpoint.ckpt`` (the async buffer lanes are part of
  the state pytree, so async runs resume bit-identically too); the
  telemetry run manifest rides along as provenance.

The telemetry plane lives next door in :mod:`repro.fl.obs`: pass a
``RunRecorder`` as ``Engine(telemetry=...)`` to get phase-span wall
times and structured per-round JSONL events — instrumentation is
read-only and conformance-pinned to never perturb the round
(``docs/observability.md``).

See ``README.md`` next to this file for the backend architecture and
how to run the conformance matrix locally, and ``docs/`` at the repo
root for the subsystem architecture and the async device-buffer design.
"""
from repro.fl.runtime.codec import CodecConfig          # noqa: F401
from repro.fl.runtime.engine import (                   # noqa: F401
    BACKENDS, Engine, EngineState, RoundReport, RuntimeConfig)
from repro.fl.runtime.executors import (                # noqa: F401
    COLLECTIVES, InProcessExecutor, ShardMapExecutor,
    build_sharded_async_update, build_sharded_round)
from repro.fl.runtime.scheduler import (                # noqa: F401
    Participation, Scheduler, SchedulerConfig)
from repro.fl.runtime.strategy import (                 # noqa: F401
    DOWNLOADS, FedAvgStrategy, FedTMStrategy, FLISStrategy, IFCAStrategy,
    ServerState, Strategy, TPFLStrategy, Upload, build_baseline_strategy,
    default_server_update)
