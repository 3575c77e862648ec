"""Open-loop personalized serving through ``ServingPlane.predict``.

Set-up makes the population (every client's TA bank and weights) on the
device from the seed in one jitted call, activates it in a serving
plane through the plane's own ``refresh``, and warms every shape the
window can send: each batch size the dispatcher pads to (powers of two
up to ``max_batch``) with every count of distinct clients it can hold.

The schedule is fixed by the traffic file and the seed: ``rate_rps ×
seconds`` requests whose gaps are the exponential distribution's
quantiles in a seeded order (every seed offers the same set of gaps),
client ids from the Zipf law by quantile over a seeded ranking of the
clients, one test sample of that client each.  A generator thread
enqueues each request at its due time; the dispatcher hands the plane
every queued request, up to ``max_batch``, whenever the plane is free,
padding the batch to the next power of two with copies of its last
request.  Latency runs from a request's due time to its prediction on
the host; ``serve_p95_ms`` is the 95th percentile over all requests of
the window (one never answered counts as infinitely late) and
``serve_rps`` the requests answered inside the window over its length.
"""
from __future__ import annotations

import collections
import gc
import math
import threading
import time
import types

import numpy as np

import counts
import harness
import scenario
import trace_reduce

ANNOTATION = "bench.predict"


class MemoryRegistry:
    """The two calls a serving plane makes of its registry (``latest``,
    ``pull``), answered from one version held in memory."""

    def __init__(self, state):
        self._state = state

    def latest(self) -> int:
        return 1

    def pull(self, version, like):
        del version, like
        return self._state


def population(seed: int, cfg: dict, tr: dict):
    """Every client's TA bank and weights, from the seed, in one jitted
    call: each literal is included with the traffic file's density (per
    clause, on the plain and on the negated half of the literals), an
    included automaton sits uniformly in [N+1, 2N], an excluded one in
    [1, N]; weights are uniform in [1, weight_max]."""
    import jax
    import jax.numpy as jnp

    n, C, m = cfg["population"], cfg["n_classes"], cfg["n_clauses"]
    o, N = cfg["n_features"], cfg["n_states"]
    per = tr["includes_per_clause"]
    dens = np.concatenate([np.full(o, per["plain"] / o, np.float32),
                           np.full(o, per["negated"] / o, np.float32)])

    @jax.jit
    def make(key):
        k_inc, k_st, k_w = jax.random.split(key, 3)
        shape = (n, C, m, 2 * o)
        inc = jax.random.uniform(k_inc, shape) < jnp.asarray(dens)
        st = jax.random.randint(k_st, shape, 0, N, jnp.int32)
        ta = jnp.where(inc, N + 1 + st, 1 + st)
        w = jax.random.randint(k_w, (n, C, m), 1, tr["weight_max"] + 1,
                               jnp.int32)
        return ta, w

    key = jax.random.fold_in(jnp.asarray(harness.key_data(seed)), 2)
    return jax.block_until_ready(make(key))


def schedule(seed: int, tr: dict, seconds: float, n_clients: int,
             n_test: int) -> dict:
    """Due times (s from the window's start), client ids and test-sample
    indices of every request of one window."""
    rate = float(tr["rate_rps"])
    n = max(1, round(rate * seconds))
    rng = np.random.default_rng(int(seed) & 0xFFFFFFFFFFFFFFFF)
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-u) / rate)
    due = np.cumsum(gaps)
    ranks = np.arange(1, n_clients + 1, dtype=np.float64)
    p = ranks ** -float(tr["zipf_exponent"])
    rank_of = np.searchsorted(np.cumsum(p / p.sum()), u)
    clients = rng.permutation(n_clients)[rng.permutation(rank_of)]
    return {"due": due, "client": clients.astype(np.int64),
            "sample": rng.integers(0, n_test, n)}


def _pow2(r: int) -> int:
    return 1 << (r - 1).bit_length()


def _batch(ids: np.ndarray, xs: np.ndarray, size: int):
    pad = size - ids.size
    return (np.concatenate([ids, np.repeat(ids[-1:], pad)]),
            np.concatenate([xs, np.repeat(xs[-1:], pad, axis=0)]))


def warm_up(plane, x_test: np.ndarray, max_batch: int) -> int:
    """Every (batch size, distinct clients) pair the dispatcher can
    send; returns the number of calls."""
    calls, size = 0, 1
    while size <= max_batch:
        for u in range(1, size + 1):
            ids = np.arange(u, dtype=np.int64) % x_test.shape[0]
            plane.predict(*_batch(ids, x_test[ids, 0], size))
            calls += 1
        size *= 2
    return calls


def serve_window(plane, sched: dict, x_test: np.ndarray, max_batch: int,
                 annotate: bool = False, grace: float = 60.0) -> dict:
    """Offer ``sched`` open-loop; returns per-request due / enqueue /
    done times (absolute, ``perf_counter``), predictions, and per-call
    (size, distinct clients, host seconds)."""
    import jax

    due_rel = sched["due"]
    n = due_rel.size
    queue: collections.deque = collections.deque()
    cv = threading.Condition()
    enq = np.full(n, np.nan)
    done = np.full(n, np.nan)
    preds = np.full(n, -1, np.int64)
    calls = []
    t_start = time.perf_counter() + 0.05
    due = t_start + due_rel

    def generate():
        for i in range(n):
            delay = due[i] - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            with cv:
                queue.append(i)
                enq[i] = time.perf_counter()
                cv.notify()

    gen = threading.Thread(target=generate, daemon=True)
    gen.start()
    served, deadline = 0, t_start + due_rel[-1] + grace
    try:
        while served < n and time.perf_counter() < deadline:
            with cv:
                if not queue:
                    cv.wait(timeout=0.5)
                    continue
                take = [queue.popleft()
                        for _ in range(min(len(queue), max_batch))]
            idx = np.asarray(take)
            ids = sched["client"][idx]
            xs = x_test[ids, sched["sample"][idx]]
            t0 = time.perf_counter()
            if annotate:
                with jax.profiler.TraceAnnotation(ANNOTATION):
                    out = plane.predict(*_batch(ids, xs, _pow2(idx.size)))
            else:
                out = plane.predict(*_batch(ids, xs, _pow2(idx.size)))
            t1 = time.perf_counter()
            done[idx] = t1
            preds[idx] = out[:idx.size]
            calls.append((idx.size, int(np.unique(ids).size), t1 - t0))
            served += idx.size
    finally:
        gen.join(timeout=grace)
    return {"t_start": t_start, "due": due, "enq": enq, "done": done,
            "preds": preds, "calls": calls}


def window_metrics(w: dict, seconds: float) -> dict:
    """serve_p95_ms over all requests (never answered = infinitely
    late), serve_rps over the window, and how late the generator ran."""
    lat = np.where(np.isnan(w["done"]), np.inf, w["done"] - w["due"])
    srt = np.sort(lat)
    p95 = srt[max(0, math.ceil(0.95 * srt.size) - 1)]
    answered = np.sum(w["done"] <= w["t_start"] + seconds)
    return {"serve_p95_ms": float(p95 * 1e3),
            "serve_rps": float(answered / seconds),
            "gen_lag_s": (w["enq"] - w["due"]).tolist(),
            "failed": int(np.isnan(w["done"]).sum())}


def _segment(sched: dict, lo: float, hi: float) -> dict:
    keep = (sched["due"] >= lo) & (sched["due"] < hi)
    out = {k: v[keep] for k, v in sched.items()}
    out["due"] = out["due"] - lo
    return out


def build_plane(ctx: harness.Context):
    """The serving plane with the seed's population active and every
    shape warm, and the clients' test samples on the host."""
    from repro.core.tm import TMParams
    from repro.fl.serve import ServingPlane

    parts = ctx.setup_parts
    t = time.perf_counter()
    data = scenario.client_data(ctx)
    x_test = np.asarray(data.x_test)
    del data
    parts["data_s"] = time.perf_counter() - t

    t = time.perf_counter()
    ta, w = population(ctx.seed, ctx.config, ctx.traffic)
    state = types.SimpleNamespace(
        client_state=TMParams(ta_state=ta, weights=w))
    plane = ServingPlane(scenario.serving_strategy(ctx.config),
                         MemoryRegistry(state), like=None)
    if not plane.refresh():
        raise RuntimeError("the serving plane activated no model")
    parts["state_s"] = time.perf_counter() - t
    t = time.perf_counter()
    parts["warmup_calls"] = warm_up(plane, x_test,
                                    ctx.traffic["max_batch"])
    parts["warmup_s"] = time.perf_counter() - t
    return plane, x_test


def run(ctx: harness.Context) -> harness.Outcome:
    import jax
    from repro.fl.serve.telemetry import ServeTelemetry

    cfg, tr = ctx.config, ctx.traffic
    parts = ctx.setup_parts
    plane, x_test = build_plane(ctx)
    setup_s = time.perf_counter() - ctx.t0

    C, m, L = cfg["n_classes"], cfg["n_clauses"], 2 * cfg["n_features"]
    records: dict = {"peak": cfg["peak"],
                     "device_kind": jax.devices()[0].device_kind}
    outcome = harness.Outcome(attempted=0, failed=0, end_to_end={},
                              records=records, checks=[],
                              memory_peak_bytes=0)
    if not ctx.trace:
        sched = schedule(ctx.seed, tr, ctx.seconds, cfg["population"],
                         x_test.shape[1])
        with harness.CompileCounter() as compiles:
            win = serve_window(plane, sched, x_test, tr["max_batch"])
        parts["window_compiles"] = compiles.n
        got = window_metrics(win, ctx.seconds)
        outcome.end_to_end = {"setup_s": setup_s,
                              "serve_p95_ms": got["serve_p95_ms"],
                              "serve_rps": got["serve_rps"]}
        records["gen_lag_s"] = got["gen_lag_s"]
    else:
        ts, ss = tr["trace_seconds"], tr["span_seconds"]
        sched = schedule(ctx.seed, tr, ts + ss, cfg["population"],
                         x_test.shape[1])
        # part 1: the device trace, telemetry off
        tdir = ctx.work_dir / "trace"
        trace_reduce.start(tdir)
        w1 = serve_window(plane, _segment(sched, 0.0, ts), x_test,
                          tr["max_batch"], annotate=True)
        jax.profiler.stop_trace()
        red = trace_reduce.reduce_dir(tdir, ANNOTATION,
                                      ["tm_fused_votes_batched"])
        calls = w1["calls"]
        red["calls"] = len(calls)
        if harness.take_trace(outcome, red):
            records["serve_work"] = {
                "ops": [counts.tm_predict_ops(r, C, m, L)
                        for r, _, _ in calls],
                "bytes": [counts.tm_predict_bytes(u, C, m, L)
                          for _, u, _ in calls],
                "predict_s": sum(s for _, _, s in calls)}
        # part 2: the plane's resolve / predict spans (fenced)
        plane.obs = ServeTelemetry(ctx.work_dir / "spans")
        w2 = serve_window(plane, _segment(sched, ts, ts + ss), x_test,
                          tr["max_batch"])
        records["resolve_s"] = [
            e["phases"]["serve/resolve"] for e in _events(ctx)
            if e.get("event") == "batch"]
        win = {k: np.concatenate([w1[k], w2[k]])
               for k in ("due", "enq", "done", "preds")}
        sched = {k: np.concatenate([_segment(sched, 0.0, ts)[k],
                                    _segment(sched, ts, ts + ss)[k]])
                 for k in ("client", "sample")}
        records["gen_lag_s"] = (win["enq"] - win["due"]).tolist()
    outcome.attempted = int(win["due"].size)
    outcome.failed = int(np.isnan(win["done"]).sum())
    outcome.memory_peak_bytes = harness.memory_peak_bytes()

    # the reference, once the program's state is freed
    del plane
    gc.collect()
    outcome.checks = check(ctx, sched, win, x_test)
    return outcome


def _events(ctx: harness.Context) -> list[dict]:
    import json
    path = ctx.work_dir / "spans" / "serve_events.jsonl"
    return [json.loads(line) for line in path.read_text().splitlines()
            if line]


def sample_answered(seed: int, win: dict, k: int) -> np.ndarray:
    """A sample, drawn from the seed, of the answered requests."""
    answered = np.flatnonzero(~np.isnan(win["done"]))
    rng = np.random.default_rng((int(seed) & 0xFFFFFFFFFFFFFFFF) ^ 0x5E)
    return np.sort(rng.choice(answered, min(k, answered.size),
                              replace=False))


def reference_predictions(ctx: harness.Context, sched: dict, idx,
                          x_test: np.ndarray, control: str | None = None):
    """The plain reference's predictions for requests ``idx``, on the
    population rebuilt from the seed, in blocks of rows.  ``control``:
    ``"bf16"`` sums the votes in bfloat16; ``"other_client"`` answers
    each request with the next client's model, breaking the
    personalization the configuration guarantees."""
    ref = harness.load_module("reference", ctx.config["reference"])
    fn = ref.predict_control if control == "bf16" else ref.predict
    shift = 1 if control == "other_client" else 0
    ta, w = population(ctx.seed, ctx.config, ctx.traffic)
    out = []
    block = ctx.traffic["max_batch"]
    for lo in range(0, len(idx), block):
        part = idx[lo:lo + block]
        ids = sched["client"][part]
        rows = (ids + shift) % ta.shape[0]
        out.append(fn(ta[rows], w[rows], x_test[ids, sched["sample"][part]],
                      ctx.config))
    return np.concatenate(out) if out else np.zeros(0, np.int64)


def check(ctx: harness.Context, sched: dict, win: dict,
          x_test: np.ndarray) -> list[harness.Check]:
    idx = sample_answered(ctx.seed, win, ctx.traffic["check_sample"])
    want = reference_predictions(ctx, sched, idx, x_test)
    limits = ctx.config["limits"]["serve"]
    got = {"mismatches": float(np.sum(win["preds"][idx] != want)),
           "unanswered": float(np.isnan(win["done"]).sum()),
           "compared_short": float(ctx.traffic["check_sample"] - idx.size)}
    return [harness.Check(k, got[k], limits[k]) for k in limits]
