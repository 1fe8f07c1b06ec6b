"""Device time per program stage, and idle gaps named by program spans.

The program (``repro.obs``) names its device stages with
``jax.named_scope`` (``ivf.probe``, ``ivf.gather``, ``ivf.scan``,
``lloyd.assign``, ``lloyd.update``, ...) and, with tracing on, writes its
host spans into the profiler's trace as ``repro.<name>`` events. A TPU
trace carries each device op's scope path as the op's ``tf_op`` stat
(``jit(_ivf_search)/ivf.gather/...``).

``load`` reads an ``.xplane.pb`` as ``bench.trace.load`` does, but keeps
each device op's scope path as a fourth field and the host's ``repro.*``
events beside the ``bench.*`` ones. ``reduce`` gives what
``bench.trace.reduce`` gives on the same trace (``window_s``, ``busy_s``,
``device_ops`` read by the benchmark's spans alone), plus ``stage_s``, the
device seconds of innermost ops per stage scope, and ``idle_gaps`` named
by the innermost span, program or benchmark, that covers most of each
gap (more than half of it). Both work on plain dicts, so they are
checked on recorded traces without a chip.

This module stands beside ``bench.trace`` until ``bench/run.py`` collects
these numbers itself (``bench/program_trace.py`` applies them meanwhile);
then the fourth field and ``stage_s`` belong in ``bench.trace`` and this
module goes.
"""
from __future__ import annotations

import glob
import os
import re

from bench import trace as tr

PROGRAM_PREFIX = "repro."
SCOPE_STAT = "tf_op"
HLO_STAT = "Hlo Proto"
METADATA_PLANE = "/host:metadata"
STAGE = re.compile(r"^(ivf|lloyd)\.[a-z_]+$")


def load(directory: str) -> dict:
    """The newest ``.xplane.pb`` under ``directory`` as a plain dict:
    device op events ``[name, start_ns, dur_ns, scope]`` and the host's
    ``bench.*`` and ``repro.*`` events ``[name, start_ns, dur_ns]``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    with open(paths[-1], "rb") as f:
        raw = f.read()
    scopes = device_scopes(raw)
    planes = []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        on_device = plane.name.startswith(tr.DEVICE_PLANE)
        scope_of = scopes.get(plane.name, {})
        lines = []
        for line in plane.lines:
            if on_device and line.name != tr.OPS_LINE:
                continue
            if on_device:
                evs = [[e.name, int(e.start_ns), int(e.duration_ns),
                        scope_of.get(e.name, "")] for e in line.events]
            else:
                evs = [[e.name, int(e.start_ns), int(e.duration_ns)]
                       for e in line.events
                       if e.name.startswith((tr.SPAN_PREFIX,
                                             PROGRAM_PREFIX))]
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_scopes(raw: bytes) -> dict[str, dict[str, str]]:
    """``{device plane: {op name: scope path}}`` from a serialized XSpace.

    An op's scope is the ``tf_op`` stat of its event metadata, which
    ``jax.profiler.ProfileData`` does not expose. An op the compiler made
    carries none (XLA splits a large gather in two and joins the halves
    in a fusion of its own); it takes the scope of its first operand that
    has one, from the program's HLO, which the trace keeps in its
    ``/host:metadata`` plane. An op name that two metadata entries give
    different scopes maps to no scope."""
    planes = {}
    for num, plane in _fields(memoryview(raw)):
        if num == 1:                                   # XSpace.planes
            name, metas = _plane(plane)
            planes[name] = metas
    inherited = {}                                     # program id -> ops
    for meta in planes.get(METADATA_PLANE, ()):
        program = re.search(r"\((\d+)\)$", meta["name"])
        if program and HLO_STAT in meta["stats"]:
            inherited[int(program.group(1))] = _hlo_scopes(
                meta["stats"][HLO_STAT])
    out = {}
    for name, metas in planes.items():
        if not name.startswith(tr.DEVICE_PLANE):
            continue
        ops: dict[str, str] = {}
        for meta in metas:
            scope = meta["stats"].get(SCOPE_STAT, "")
            if not scope:
                program = inherited.get(meta["stats"].get("program_id"), {})
                scope = program.get(meta["display"], "")
            op = meta["name"]
            ops[op] = scope if ops.get(op, scope) == scope else ""
        out[name] = ops
    return out


def _plane(plane: memoryview) -> tuple[str, list[dict]]:
    """An XPlane's name and its event metadata, each as ``{"name",
    "display", "stats": {stat name: value}}``."""
    name, metas, stat_names = "", [], {}
    for n, v in _fields(plane):
        if n == 2:                                     # XPlane.name
            name = bytes(v).decode()
        elif n == 4:                                   # event_metadata
            metas.append(_map_value(v))
        elif n == 5:                                   # stat_metadata
            sm = dict(_fields(_map_value(v)))
            stat_names[sm.get(1, 0)] = bytes(sm.get(2, b"")).decode()
    out = []
    for meta in metas:
        m = {"name": "", "display": "", "stats": {}}
        for n, v in _fields(meta):
            if n == 2:                                 # XEventMetadata.name
                m["name"] = bytes(v).decode()
            elif n == 4:                               # .display_name
                m["display"] = bytes(v).decode()
            elif n == 5:                               # .stats: XStat
                st = dict(_fields(v))
                key = stat_names.get(st.get(1, 0), "")
                if 5 in st:                            # str_value
                    m["stats"][key] = bytes(st[5]).decode()
                elif 6 in st:                          # bytes_value
                    m["stats"][key] = st[6]
                elif 7 in st:                          # ref_value
                    m["stats"][key] = stat_names.get(st[7], "")
                else:                                  # uint64, int64
                    m["stats"][key] = st.get(3, st.get(4))
        m["display"] = m["display"] or tr.op_name(m["name"])
        out.append(m)
    return name, out


def _hlo_scopes(proto: memoryview) -> dict[str, str]:
    """``{instruction name: op_name}`` of a serialized HloProto, an
    instruction without one taking its first scoped operand's (the
    instructions of a computation come operands first)."""
    module = _map_value(proto, field=1)                # HloProto.hlo_module
    scopes, by_id = {}, {}
    for n, comp in _fields(module):
        if n != 3:                                     # .computations
            continue
        for k, inst in _fields(comp):
            if k != 2:                                 # .instructions
                continue
            name, scope, iid, operands = "", "", None, []
            for f, v in _fields(inst):
                if f == 1:                             # .name
                    name = bytes(v).decode()
                elif f == 7:                           # .metadata.op_name
                    scope = bytes(_map_value(v, field=2)).decode()
                elif f == 35:                          # .id
                    iid = v
                elif f == 36:                          # .operand_ids
                    operands += (_packed(v) if isinstance(v, memoryview)
                                 else [v])
            scope = scope or next((by_id[o] for o in operands
                                   if by_id.get(o)), "")
            by_id[iid] = scopes[name] = scope
    return scopes


def _packed(buf: memoryview) -> list[int]:
    out, i = [], 0
    while i < len(buf):
        v, i = _varint(buf, i)
        out.append(v)
    return out


def _map_value(entry: memoryview, field: int = 2) -> memoryview:
    """Field ``field`` of a serialized message (by default the value of a
    map entry); empty where it is absent."""
    return next((v for n, v in _fields(entry) if n == field),
                memoryview(b""))


def _fields(buf: memoryview):
    """``(field number, value)`` pairs of a serialized protobuf message:
    ints for varint and fixed-width fields, sub-buffers for
    length-delimited ones."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif kind == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, value


def _varint(buf: memoryview, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _leaves(events: list) -> list:
    """The events that hold no other (the XLA Ops line nests an op's
    body under a ``while`` or ``call``), as ``bench.trace`` takes them."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    return [e for e, nxt in zip(evs, evs[1:] + [None])
            if nxt is None or nxt[1] >= e[1] + e[2]]


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo: int, hi: int):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def stage(scope: str) -> str | None:
    """The innermost stage scope (``ivf.gather``) on an op's scope path,
    or None for an op outside every stage."""
    for part in reversed(scope.split("/")):
        if STAGE.match(part.rstrip(":")):
            return part.rstrip(":")
    return None


def _bench_view(trace: dict) -> dict:
    """The trace as ``bench.trace.reduce`` reads it: three-field device
    events and the benchmark's own spans only."""
    planes = []
    for plane in trace["planes"]:
        device = plane["name"].startswith(tr.DEVICE_PLANE)
        lines = []
        for line in plane["lines"]:
            evs = [e[:3] for e in line["events"]
                   if device or e[0].startswith(tr.SPAN_PREFIX)]
            if evs:
                lines.append({"name": line["name"], "events": evs})
        if lines:
            planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}


def _span_name(event_name: str) -> str:
    for prefix in (tr.SPAN_PREFIX, PROGRAM_PREFIX):
        if event_name.startswith(prefix):
            return event_name[len(prefix):]
    return event_name


def reduce(trace: dict) -> dict | None:
    """``bench.trace.reduce``'s numbers, ``stage_s`` and ``idle_gaps``
    named by the innermost covering span; None where that gives None."""
    base = tr.reduce(_bench_view(trace))
    if base is None:
        return None
    spans, devices = [], []
    for plane in trace["planes"]:
        for line in plane["lines"]:
            if plane["name"].startswith(tr.DEVICE_PLANE):
                devices.append(line["events"])
            else:
                spans += [(_span_name(e[0]), e[1], e[1] + e[2])
                          for e in line["events"]]
    win = [(s, e) for n, s, e in spans if n == tr.WINDOW_SPAN]
    lo, hi = win[0][0], win[-1][1]
    inner = [sp for sp in spans if sp[0] != tr.WINDOW_SPAN]
    stage_ns: dict[str, int] = {}
    gaps = []
    for events in devices:
        for e in _leaves(events):
            name = stage(e[3]) if len(e) > 3 else None
            a, b = max(e[1], lo), min(e[1] + e[2], hi)
            if name and b > a:
                stage_ns[name] = stage_ns.get(name, 0) + (b - a)
        merged = _union(_clip([(s, s + d) for _n, s, d, *_ in events],
                                    lo, hi))
        edges = [lo] + [v for iv in merged for v in iv] + [hi]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    n_dev = len(devices)
    gaps.sort(key=lambda g: g[0] - g[1])
    return dict(
        base,
        stage_s={n: t / n_dev / 1e9 for n, t in sorted(stage_ns.items())},
        idle_gaps=[[label(inner, a, b), (b - a) / 1e9]
                   for a, b in gaps[:tr.TOP]])


def label(spans: list[tuple[str, int, int]], a: int, b: int) -> str:
    """The name of the innermost (shortest) span that covers more than
    half of the gap ``[a, b)``; where none does, of the span that covers
    most of it, or ``no span`` where the host was in no span for longer."""
    hits = [(min(b, e) - max(a, s), e - s, n) for n, s, e in spans
            if min(b, e) > max(a, s)]
    most = [h for h in hits if 2 * h[0] > b - a]
    if most:
        return min(most, key=lambda h: h[1])[2]
    covered = _union([(max(a, s), min(b, e)) for _n, s, e in spans
                         if min(b, e) > max(a, s)])
    free = (b - a) - sum(y - x for x, y in covered)
    best = max(hits, default=(0, 0, "no span"))
    return best[2] if best[0] >= free else "no span"
