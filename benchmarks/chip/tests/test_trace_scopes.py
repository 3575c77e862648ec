"""The reduction of named scopes and engine stages (``trace_scopes``):
the arithmetic by hand on a made-up trace, then on a small trace
recorded on the chip, checked against independent formulas.

    PYTHONPATH=src python -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import trace_reduce  # noqa: E402
import trace_scopes  # noqa: E402

TESTDATA = BENCH / "testdata"
SCOPES = ["tm.draws", "tm.epoch_pad"]


def _made_up() -> dict:
    """One round over [0, 100] ns.  Host: ``engine.a`` [10, 60] holding
    ``engine.b`` [20, 30]; ``engine.c`` [70, 90]; an ``engine.x`` on
    another thread.  Device: a draw [0, 15]; a pad [40, 50] holding a
    vmapped draw [42, 45]; another op [80, 85]."""
    host = [["bench.run_round", 0, 100, "python", {}],
            ["engine.a", 10, 50, "python", {"round": 3}],
            ["engine.b", 20, 10, "python", {"round": 3}],
            ["engine.c", 70, 20, "python", {"round": 3}],
            ["engine.x", 0, 100, "other thread", {"round": 3}]]
    ops = [["%fusion.1 = ...", 0, 15, "jit(f)/while/body/tm.draws/mul"],
           ["%pad.2 = ...", 40, 10, "jit(f)/jit(g)/tm.epoch_pad/pad"],
           ["%fusion.3 = ...", 42, 3, "jit(f)/vmap(tm.draws)/threefry"],
           ["%copy.4 = ...", 80, 5, "jit(f)/not.tm.draws.here/copy"]]
    return {"device": [{"plane": "/device:TPU:0", "ops": ops}],
            "host": host}


def test_scopes_and_stage_idle_by_hand():
    red = trace_scopes.reduce(_made_up(), "bench.run_round", SCOPES)
    assert red["rounds"] == 1
    # draws: [0, 15] whole and the nested [42, 45]; pad: 10 less 3
    assert red["scope_s"]["tm.draws"] == pytest.approx(18e-9)
    assert red["scope_s"]["tm.epoch_pad"] == pytest.approx(7e-9)
    # gaps [15, 40], [50, 80], [85, 100]: a 5 + 10 + 10, b 10, c 10 + 5,
    # no stage 10 + 10; the other thread's span is not the round's
    idle = {k: v * 1e9 for k, v in red["stage_idle_s"].items()}
    assert idle == pytest.approx({"a": 25, "b": 10, "c": 15,
                                  "(no stage)": 20})


def test_scope_is_a_whole_path_component():
    assert trace_scopes.in_scope("jit(f)/tm.draws/mul", "tm.draws")
    assert trace_scopes.in_scope("vmap(tm.draws)/x", "tm.draws")
    assert trace_scopes.in_scope("tm.draws", "tm.draws")
    assert not trace_scopes.in_scope("jit(f)/tm.drawsx/mul", "tm.draws")
    assert not trace_scopes.in_scope("jit(f)/xtm.draws/mul", "tm.draws")


def test_trace_without_the_annotation_is_refused():
    with pytest.raises(ValueError, match="no host event named"):
        trace_scopes.reduce(_made_up(), "not.there", SCOPES)


# -- a small trace recorded on the chip ---------------------------------------

def _recorded() -> dict:
    return json.loads((TESTDATA / "trace_scopes_small.json").read_text())


def _window(ev):
    ann = [(h[1], h[1] + h[2]) for h in ev["host"]
           if h[0] == ev["annotation"]]
    return min(a for a, _ in ann), max(b for _, b in ann), len(ann)


def test_recorded_scopes_are_their_ops_own_time():
    ev = _recorded()
    red = trace_scopes.reduce(ev, ev["annotation"], SCOPES)
    lo, hi, rounds = _window(ev)
    ops = ev["device"][0]["ops"]
    iv = [(max(s, lo), min(s + d, hi)) for _, s, d, _ in ops
          if min(s + d, hi) > max(s, lo)]
    names = [op for _, s, d, op in ops if min(s + d, hi) > max(s, lo)]
    # own time by a quadratic sweep: each op less the ops directly inside
    own = []
    for i, (s, e) in enumerate(iv):
        inside = [j for j, (s2, e2) in enumerate(iv)
                  if j != i and s <= s2 and e2 <= e
                  and (s2, -e2) > (s, -e)]
        direct = [j for j in inside if not any(
            k != j and iv[k][0] <= iv[j][0] and iv[j][1] <= iv[k][1]
            for k in inside)]
        own.append(e - s - sum(iv[j][1] - iv[j][0] for j in direct))
    for sc in SCOPES:
        want = sum(o for o, n in zip(own, names)
                   if trace_scopes.in_scope(n, sc))
        assert want > 0, sc
        assert red["scope_s"][sc] == pytest.approx(want / 1e9 / rounds)


def test_recorded_stage_idle_is_each_spans_idle_less_its_childrens():
    ev = _recorded()
    red = trace_scopes.reduce(ev, ev["annotation"], SCOPES)
    lo, hi, rounds = _window(ev)
    ops = ev["device"][0]["ops"]
    busy = trace_reduce._union([(max(s, lo), min(s + d, hi))
                                for _, s, d, _ in ops
                                if min(s + d, hi) > max(s, lo)])

    def idle_in(a, b):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            return 0
        return (b - a) - sum(max(0, min(e, b) - max(s, a)) for s, e in busy)

    line = next(h[3] for h in ev["host"] if h[0] == ev["annotation"])
    spans = [(h[1], h[1] + h[2], h[0][len("engine."):])
             for h in ev["host"]
             if h[0].startswith("engine.") and h[3] == line]
    want: dict[str, int] = {}
    for s, e, name in spans:
        kids = [(s2, e2) for s2, e2, _ in spans if (s2, e2) != (s, e)
                and s <= s2 and e2 <= e]
        direct = [k for k in kids if not any(
            k2 != k and k2[0] <= k[0] and k[1] <= k2[1] for k2 in kids)]
        want[name] = want.get(name, 0) + idle_in(s, e) - sum(
            idle_in(*k) for k in direct)
    got = red["stage_idle_s"]
    for name, ns in want.items():
        assert got.get(name, 0.0) == pytest.approx(ns / 1e9 / rounds,
                                                    abs=1e-12)
    # the stages and the rest make up the window's idle time
    total_idle = (hi - lo) - sum(e - s for s, e in busy)
    assert sum(got.values()) == pytest.approx(total_idle / 1e9 / rounds)
    assert sum(v for k, v in got.items() if k != "(no stage)") > 0
