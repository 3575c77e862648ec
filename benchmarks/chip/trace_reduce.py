"""Profiler trace → device busy and idle time, kernel time, breakdown.

``start(dir)`` starts ``jax.profiler`` with its Python tracer off (it
would slow the host and inflate the idle share it is read for).
``load(path)`` reads the ``.xplane.pb`` into plain events: ``device``
(per TPU plane, the ``XLA Ops`` line: HLO text, start, duration in ns)
and ``host`` (every host thread line).  ``reduce`` works on those plain
events, so a small recorded trace checked in as JSON exercises the same
arithmetic:

* the window runs from the first to the last host event named
  ``annotation`` (the harness wraps each timed call in one);
* busy time is the union of a device's op intervals inside the window,
  averaged over the devices; idle share is 1 − busy / window;
* ops nest (a ``while`` holds its body's ops), so each op is charged
  its self time: its duration less that of the ops inside it;
* a kernel's time is the self time of the ops named after it (the HLO
  instruction's own name, before ``=``); everything else is outside
  the kernels;
* the breakdown lists the ten ops that took most self time, and the
  idle gaps of the first device summed by what the host was doing: the
  shortest host event that covers the gap's midpoint.
"""
from __future__ import annotations

import glob
import pathlib
import re

import numpy as np

_SUFFIX = re.compile(r"\.\d+$")


def start(trace_dir: str | pathlib.Path) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def load(path: str | pathlib.Path) -> dict:
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    device, host = [], []
    for plane in pd.planes:
        if "/device:TPU:" in plane.name:
            ops = [[e.name, e.start_ns, e.duration_ns]
                   for line in plane.lines if line.name == "XLA Ops"
                   for e in line.events]
            device.append({"plane": plane.name, "ops": ops})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns, line.name]
                            for e in line.events)
    device.sort(key=lambda d: d["plane"])
    return {"device": device, "host": host}


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` → ``fusion.12``."""
    return hlo.split(" = ", 1)[0].strip().lstrip("%")


def _self_times(iv: list[tuple[int, int]]) -> list[int]:
    order = sorted(range(len(iv)), key=lambda i: (iv[i][0], -iv[i][1]))
    own = [e - s for s, e in iv]
    stack: list[int] = []
    for i in order:
        s, e = iv[i]
        while stack and iv[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, iv[stack[-1]][1]) - s
        stack.append(i)
    return own


def _union(iv: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def reduce(events: dict, annotation: str, kernels: list[str]) -> dict:
    ann = [(s, s + d) for name, s, d, _ in events["host"]
           if name == annotation]
    if not ann:
        raise ValueError(f"no host event named {annotation!r} in the trace")
    lo, hi = min(s for s, _ in ann), max(e for _, e in ann)
    busy, kernel_ns, outside_ns, per_op = [], {k: 0 for k in kernels}, 0, {}
    first_union: list[tuple[int, int]] = []
    for i, dev in enumerate(events["device"]):
        names, iv = [], []
        for name, s, d in dev["ops"]:
            s2, e2 = max(s, lo), min(s + d, hi)
            if e2 > s2:
                names.append(name)
                iv.append((s2, e2))
        for name, own in zip(names, _self_times(iv)):
            base = _SUFFIX.sub("", op_name(name))
            if base in kernel_ns:
                kernel_ns[base] += own
            else:
                outside_ns += own
            key = name[:120]
            per_op[key] = per_op.get(key, 0) + own
        u = _union(iv)
        busy.append(sum(e - s for s, e in u))
        if i == 0:
            first_union = u
    n_dev = max(len(events["device"]), 1)
    gaps, prev = [], lo
    for s, e in first_union:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        gaps.append((prev, hi))
    names = [h[0] for h in events["host"]]
    hs = np.asarray([h[1] for h in events["host"]], np.float64)
    hd = np.asarray([h[2] for h in events["host"]], np.float64)
    idle_by: dict[str, int] = {}
    for s, e in gaps:
        mid = (s + e) / 2
        cover = np.flatnonzero((hs <= mid) & (hs + hd >= mid))
        what = (names[cover[np.argmin(hd[cover])]] if cover.size
                else "(no host event)")
        idle_by[what] = idle_by.get(what, 0) + (e - s)

    def top(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10] if v > 0]

    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy) / n_dev / 1e9,
            "kernel_s": {k: v / 1e9 / n_dev for k, v in kernel_ns.items()},
            "outside_s": outside_ns / n_dev / 1e9,
            "devices": len(events["device"]),
            "breakdown": {"device_ops": top(per_op),
                          "idle_gaps": top(idle_by)}}


def reduce_dir(trace_dir: str | pathlib.Path, annotation: str,
               kernels: list[str]) -> dict:
    paths = glob.glob(str(pathlib.Path(trace_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one trace under {trace_dir}, found "
                         f"{paths}")
    return reduce(load(paths[0]), annotation, kernels)
