"""Phase-span tracing for the federated round.

A :class:`PhaseTracer` times named host-side spans around the round's
stages — broadcast encode, client step, uplink codec, server-side
assign, aggregation, server_update, downlink, apply/merge, eval.  The
engine calls ``span(name)`` / ``fence(values)`` unconditionally; with
telemetry disabled both resolve to the :data:`NULL` no-ops below (a
shared null context manager and a pass), so the un-instrumented round
is exactly the pre-telemetry round.

A live span does three things:

* it times the stage with ``perf_counter``;
* it enters ``jax.profiler.TraceAnnotation("engine.<name>",
  round=<r>)``, so a profiler capture shows each stage on its host
  timeline, nested under its caller and on the device trace's clock
  (``begin_round(r)`` sets the ``round`` stat);
* it is charged the compiles JAX reports while it is the innermost
  open span: a ``jax.monitoring`` listener adds each trace, lowering
  and backend-compile event to it (``"(none)"`` outside any span).

Beside the spans, ``count(counters)`` charges the round the program's
own counters, each ``{name: per-client values}`` read off the device as
its mean over the clients (the TM kernel's ``tm.type1_hash_share``);
the null tracer's ``count`` reads nothing.

``PhaseTracer(fence=True)`` (the default) makes ``fence(values)`` a
``jax.block_until_ready``, so a span's wall time covers the device
work it launched, not just the Python dispatch.  ``fence=False`` keeps
the spans, annotations and compile counts but leaves the round's
schedule as the un-instrumented round has it: the right form under a
profiler, where the device trace gives the device time.

The **neutrality invariant**: tracing only ever *reads* — it times,
fences, and copies scalars off device.  It never feeds a value back
into the round's math, so obs-on and obs-off runs are bit-identical
(``tests/test_fl_conformance.py`` pins this across both backends and
both aggregation modes).  Fences change *when* the host waits, never
what the arrays hold.

Optional deep capture: :func:`profile_trace` wraps a run in
``jax.profiler.start_trace`` / ``stop_trace`` so ``--profile-dir`` on
``fed_train`` drops a TensorBoard-loadable device trace next to the
telemetry run directory.
"""
from __future__ import annotations

import contextlib
import time
import weakref

import jax
import numpy as np


class _NullSpan:
    """Reusable zero-cost context manager — the disabled span."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class NullTracer:
    """Telemetry off: every hook is a no-op (no timing, no fences)."""

    enabled = False

    def span(self, name: str):
        return _NULL_SPAN

    def fence(self, *values):
        pass

    def discard(self, name: str):
        pass

    def count(self, counters):
        pass

    def take(self) -> dict:
        return {}


# the compile events ``jax/_src/dispatch.py`` reports: tracing to a
# jaxpr, lowering it to MLIR, and the backend compile
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
COMPILE_EVENTS = frozenset({
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    BACKEND_COMPILE,
})
OUTSIDE = "(none)"      # where compiles outside any open span are charged


class Spans(dict):
    """One round's ``{span: seconds}``, with the compiles charged to each
    span: ``compiles`` (backend compiles) and ``compile_s`` (trace +
    lowering + backend-compile seconds), keyed by span or ``"(none)"``;
    and ``counts``, the round's program counters (``{name: value}``)."""

    def __init__(self, seconds: dict, compiles: dict, compile_s: dict,
                 counts: dict | None = None):
        super().__init__(seconds)
        self.compiles = compiles
        self.compile_s = compile_s
        self.counts = {} if counts is None else counts


class _Span:
    """One live span: an annotation on the profiler's timeline, the top
    of the tracer's span stack, and a ``perf_counter`` delta."""

    __slots__ = ("_tracer", "_name", "_t0", "_ann")

    def __init__(self, tracer: "PhaseTracer", name: str):
        self._tracer = tracer
        self._name = name

    def __enter__(self):
        tr = self._tracer
        self._ann = jax.profiler.TraceAnnotation(
            tr.annotation_prefix + self._name, **tr._stats)
        self._ann.__enter__()
        tr._stack.append(self._name)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        tr = self._tracer
        tr._stack.pop()
        self._ann.__exit__(*exc)
        tr._record(self._name, dt)
        return False


def _compile_listener(ref: "weakref.ref[PhaseTracer]"):
    """A ``jax.monitoring`` duration listener that charges compile events
    to the tracer behind ``ref`` (a weak reference, so a tracer nobody
    closes can still be collected, which unregisters the listener)."""
    def on_duration(event: str, duration: float, **kwargs) -> None:
        tracer = ref()
        if tracer is not None and event in COMPILE_EVENTS:
            tracer._on_compile(event, duration)
    return on_duration


class PhaseTracer:
    """Host-side wall-time spans, accumulated per round.

    ``span(name)`` returns a context manager; re-entering the same name
    within one round accumulates (the async host-reference loop times
    its insert per upload).  ``take()`` pops the current round's
    :class:`Spans` — the recorder calls it once per round, so spans and
    compiles never leak across rounds.  ``close()`` unregisters the
    compile listener (so does collecting the tracer).
    """

    enabled = True
    annotation_prefix = "engine."

    def __init__(self, fence: bool = True):
        self.fenced = fence
        self._spans: dict[str, float] = {}
        self._stack: list[str] = []
        self._stats: dict[str, int] = {}
        self._compiles: dict[str, int] = {}
        self._compile_s: dict[str, float] = {}
        self._counts: dict[str, float] = {}
        listener = _compile_listener(weakref.ref(self))
        jax.monitoring.register_event_duration_secs_listener(listener)
        self._unregister = weakref.finalize(
            self, jax.monitoring.unregister_event_duration_listener,
            listener)

    def begin_round(self, round_idx: int) -> None:
        """Tag the annotations of the spans opened from now on with
        ``round=round_idx``: the identifier one round's stages share."""
        self._stats = {"round": int(round_idx)}

    def span(self, name: str) -> _Span:
        return _Span(self, name)

    def _record(self, name: str, dt: float) -> None:
        self._spans[name] = self._spans.get(name, 0.0) + dt

    def _on_compile(self, event: str, duration: float) -> None:
        stage = self._stack[-1] if self._stack else OUTSIDE
        self._compile_s[stage] = self._compile_s.get(stage, 0.0) + duration
        n = self._compiles.get(stage, 0)
        self._compiles[stage] = n + (event == BACKEND_COMPILE)

    def fence(self, *values) -> None:
        """Block until every array in ``values`` (pytrees allowed) is
        computed, so the enclosing span bills the device work to the
        phase that launched it instead of whichever later phase first
        touches the result.  A no-op on an unfenced tracer."""
        if self.fenced:
            jax.block_until_ready([v for v in values if v is not None])

    def discard(self, name: str) -> None:
        """Drop a span that turned out to be vacuous (e.g. the engine
        probed an executor's fused form and it answered "no fused
        path") so events report only phases that really ran."""
        self._spans.pop(name, None)

    def count(self, counters) -> None:
        """Charge the round each ``{name: per-client values}`` counter's
        mean over the clients (a device read: the null tracer's ``count``
        reads nothing).  ``None`` is no counters."""
        for name, values in (counters or {}).items():
            value = float(np.mean(np.asarray(values)))
            self._counts[name] = self._counts.get(name, 0.0) + value

    def take(self) -> Spans:
        spans = Spans(self._spans, self._compiles, self._compile_s,
                      self._counts)
        self._spans, self._compiles, self._compile_s = {}, {}, {}
        self._counts = {}
        return spans

    def close(self) -> None:
        """Stop charging compiles to this tracer."""
        self._unregister()


NULL = NullTracer()


@contextlib.contextmanager
def profile_trace(profile_dir: str | None):
    """``jax.profiler`` capture scoped to a ``with`` block — a no-op
    when ``profile_dir`` is None (the default: span timing only)."""
    if profile_dir is None:
        yield
        return
    jax.profiler.start_trace(str(profile_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()
