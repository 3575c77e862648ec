"""Profiler trace → device time by named scope, device idle by engine stage.

The program names its work in two ways that a ``jax.profiler`` capture
keeps: ``jax.named_scope`` puts a scope (``tm.draws``, ``tm.epoch_pad``)
into the ``op_name`` metadata of the ops it traces, and the engine's
phase tracer (``fl/obs``) wraps each round stage in a host annotation
``engine.<stage>`` that carries the round index as its ``round`` stat.
``load(path)`` reads both from the ``.xplane.pb`` into plain events:
``device`` (per TPU plane, the ``XLA Ops`` line: HLO text, start and
duration in ns, op_name) and ``host`` (every host event: name, start,
duration, line, stats).  ``reduce`` works on those plain events, so a
small recorded trace checked in as JSON exercises the same arithmetic:

* the window runs from the first to the last host event named
  ``annotation``, as in ``trace_reduce``; a round is one such event;
* an op is charged its self time (its duration less that of the ops
  inside it), as in ``trace_reduce``; a scope's time is the self time
  of the ops whose op_name holds the scope as a path component — a
  fused op by the op_name XLA gave the fusion;
* each idle gap of the first device is split over the ``engine.*``
  host spans of the thread that runs the round, each instant charged to
  the innermost span covering it (``"(no stage)"`` where none does).
"""
from __future__ import annotations

import glob
import pathlib
import re

import trace_reduce

PREFIX = "engine."
NO_STAGE = "(no stage)"


def _xspace_class():
    """The ``XSpace`` message, cut to the fields read here (field numbers
    of ``tsl/profiler/protobuf/xplane.proto``).  ``ProfileData`` gives
    events and their own stats, but not the stats of an event's metadata,
    where a TPU op keeps its ``tf_op`` (the HLO op_name)."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(name="xplane_cut.proto",
                                            package="xplane_cut")

    def message(name, fields, into=fd.message_type):
        m = into.add(name=name)
        for fname, num, ftype, *rep in fields:
            f = m.field.add(name=fname, number=num,
                            label=F.LABEL_REPEATED if rep else
                            F.LABEL_OPTIONAL)
            if isinstance(ftype, str):
                f.type, f.type_name = F.TYPE_MESSAGE, f".xplane_cut.{ftype}"
            else:
                f.type = ftype
        return m

    def map_field(m, fname, num, value):
        entry = message(f"{value}Entry", [("key", 1, F.TYPE_INT64),
                                          ("value", 2, value)],
                        into=m.nested_type)
        entry.options.map_entry = True
        m.field.add(name=fname, number=num, label=F.LABEL_REPEATED,
                    type=F.TYPE_MESSAGE,
                    type_name=f".xplane_cut.{m.name}.{entry.name}")

    message("XSpace", [("planes", 1, "XPlane", True)])
    plane = message("XPlane", [("name", 2, F.TYPE_STRING),
                               ("lines", 3, "XLine", True)])
    map_field(plane, "event_metadata", 4, "XEventMetadata")
    map_field(plane, "stat_metadata", 5, "XStatMetadata")
    message("XLine", [("name", 2, F.TYPE_STRING),
                      ("timestamp_ns", 3, F.TYPE_INT64),
                      ("events", 4, "XEvent", True)])
    message("XEvent", [("metadata_id", 1, F.TYPE_INT64),
                       ("offset_ps", 2, F.TYPE_INT64),
                       ("duration_ps", 3, F.TYPE_INT64)])
    message("XStat", [("metadata_id", 1, F.TYPE_INT64),
                      ("str_value", 5, F.TYPE_STRING),
                      ("ref_value", 7, F.TYPE_UINT64)])
    message("XEventMetadata", [("name", 2, F.TYPE_STRING),
                               ("stats", 5, "XStat", True)])
    message("XStatMetadata", [("name", 2, F.TYPE_STRING)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("xplane_cut.XSpace"))


def _device_ops(path) -> list[dict]:
    """Per TPU plane, the ``XLA Ops`` line: HLO text, start and duration
    in ns on the host events' clock, and the op's ``tf_op``."""
    space = _xspace_class()()
    space.ParseFromString(pathlib.Path(path).read_bytes())
    device = []
    for plane in space.planes:
        if "/device:TPU:" not in plane.name:
            continue
        stat_name = {k: v.name for k, v in plane.stat_metadata.items()}
        op_of = {}
        for k, em in plane.event_metadata.items():
            for st in em.stats:
                if stat_name.get(st.metadata_id) == "tf_op":
                    op_of[k] = st.str_value or stat_name.get(st.ref_value, "")
        # whole ns, as ``ProfileData`` (and so ``trace_reduce``) has them
        ops = [[plane.event_metadata[e.metadata_id].name,
                line.timestamp_ns + e.offset_ps // 1000,
                e.duration_ps // 1000, op_of.get(e.metadata_id, "")]
               for line in plane.lines if line.name == "XLA Ops"
               for e in line.events]
        device.append({"plane": plane.name, "ops": ops})
    device.sort(key=lambda d: d["plane"])
    return device


def load(path: str | pathlib.Path) -> dict:
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    host = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    stats = ({k: v for k, v in e.stats
                              if isinstance(v, (int, float, str))}
                             if e.name.startswith(PREFIX) else {})
                    host.append([e.name, e.start_ns, e.duration_ns,
                                 line.name, stats])
    return {"device": _device_ops(path), "host": host}


def in_scope(op_name: str, scope: str) -> bool:
    """Whether ``scope`` is a component of the op_name path, bare or
    inside a transform's parentheses (``vmap(tm.draws)``)."""
    return re.search(r"(^|[/(])" + re.escape(scope) + r"($|[/)])",
                     op_name) is not None


def _stage_idle(gaps: list[tuple[int, int]],
                spans: list[tuple[int, int, str]]) -> dict[str, int]:
    """Split each gap over the innermost span covering each instant:
    spans on one thread nest, so the innermost is the latest to start."""
    out: dict[str, int] = {}
    for s, e in gaps:
        over = [sp for sp in spans if sp[0] < e and sp[1] > s]
        cuts = sorted({s, e} | {t for a, b, _ in over for t in (a, b)
                                if s < t < e})
        for a, b in zip(cuts, cuts[1:]):
            cover = [sp for sp in over if sp[0] <= a and sp[1] >= b]
            what = (max(cover, key=lambda sp: (sp[0], -sp[1]))[2]
                    if cover else NO_STAGE)
            out[what] = out.get(what, 0) + (b - a)
    return out


def reduce(events: dict, annotation: str, scopes: list[str]) -> dict:
    """Per round: device seconds under each scope, and device-idle
    seconds inside each engine stage (keyed by stage, no prefix)."""
    ann = [(h[1], h[1] + h[2], h[3]) for h in events["host"]
           if h[0] == annotation]
    if not ann:
        raise ValueError(f"no host event named {annotation!r} in the trace")
    lo, hi = min(a[0] for a in ann), max(a[1] for a in ann)
    rounds = len(ann)
    scope_ns = {sc: 0 for sc in scopes}
    first_union: list[tuple[int, int]] = []
    for i, dev in enumerate(events["device"]):
        names, iv = [], []
        for _, s, d, op in dev["ops"]:
            s2, e2 = max(s, lo), min(s + d, hi)
            if e2 > s2:
                names.append(op)
                iv.append((s2, e2))
        for op, own in zip(names, trace_reduce._self_times(iv)):
            for sc in scopes:
                if in_scope(op, sc):
                    scope_ns[sc] += own
        if i == 0:
            first_union = trace_reduce._union(iv)
    n_dev = max(len(events["device"]), 1)
    gaps, prev = [], lo
    for s, e in first_union:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        gaps.append((prev, hi))
    threads = {a[2] for a in ann}
    spans = [(h[1], h[1] + h[2], h[0][len(PREFIX):]) for h in events["host"]
             if h[0].startswith(PREFIX) and h[3] in threads]
    idle = _stage_idle(gaps, spans)
    return {"rounds": rounds,
            "scope_s": {sc: v / n_dev / 1e9 / rounds
                        for sc, v in scope_ns.items()},
            "stage_idle_s": {k: v / 1e9 / rounds for k, v in idle.items()}}


def reduce_dir(trace_dir: str | pathlib.Path, annotation: str,
               scopes: list[str]) -> dict:
    paths = glob.glob(str(pathlib.Path(trace_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one trace under {trace_dir}, found "
                         f"{paths}")
    return reduce(load(paths[0]), annotation, scopes)
