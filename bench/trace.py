"""Host spans, the profiler trace, and the reduction from trace to numbers.

The benchmark's own spans (``Spans``) bracket its calls into each layer
of the program. They are kept in memory on the host clock, and, while a
trace is being taken, also written into the profiler's trace as
``jax.profiler.TraceAnnotation``s named ``bench.<span>``, so that device
idle gaps can be attributed to what the host was doing.

``load`` turns an ``.xplane.pb`` into a plain dict (``{"planes": [{"name",
"lines": [{"name", "events": [[name, start_ns, dur_ns], ...]}]}]}``), and
``reduce`` works on that dict alone, so it can be checked on a small
recorded trace without a chip.
"""
from __future__ import annotations

import contextlib
import glob
import os
import shutil
import time

SPAN_PREFIX = "bench."
WINDOW_SPAN = "window"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
TOP = 10


class Spans:
    """Named host spans of one run: ``with spans("pump"): ...``."""

    def __init__(self, tracing: bool = False):
        self.tracing = tracing
        self.records: list[tuple[str, int, int]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = contextlib.nullcontext()
        if self.tracing:
            import jax
            ann = jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
        with ann:
            t0 = time.perf_counter_ns()
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter_ns()))

    def total_s(self, name: str) -> float:
        return sum(t1 - t0 for n, t0, t1 in self.records if n == name) / 1e9


@contextlib.contextmanager
def capture(directory: str):
    """Profile the block into ``directory`` (emptied first)."""
    import jax
    shutil.rmtree(directory, ignore_errors=True)
    os.makedirs(directory)
    jax.profiler.start_trace(directory)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(directory: str) -> dict:
    """The newest ``.xplane.pb`` under ``directory`` as a plain dict: the
    device planes' op lines and the host's ``bench.*`` spans."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    planes = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        on_device = plane.name.startswith(DEVICE_PLANE)
        lines = []
        for line in plane.lines:
            if on_device and line.name != OPS_LINE:
                continue
            evs = [[e.name, int(e.start_ns), int(e.duration_ns)]
                   for e in line.events
                   if on_device or e.name.startswith(SPAN_PREFIX)]
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def op_name(text: str) -> str:
    """``%name`` of an op's HLO text, or the text cut short."""
    head = text.split(" = ", 1)[0] if " = " in text else text
    return head.lstrip("%")[:120]


def _leaves(events: list) -> list:
    """The events that hold no other (the XLA Ops line nests an op's
    body under a ``while`` or ``call``)."""
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


def reduce(tr: dict) -> dict | None:
    """Busy and idle time of the device over the traced window, the device
    ops that took most time (innermost ops only, by name), and the longest
    idle gaps by the host span they fell in. None when the trace holds no
    window span or no device op in it."""
    spans, devices = [], []
    for plane in tr["planes"]:
        for line in plane["lines"]:
            if plane["name"].startswith(DEVICE_PLANE):
                devices.append(line["events"])
            else:
                spans += [(n[len(SPAN_PREFIX):], s, s + d)
                          for n, s, d in line["events"]]
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not win or not devices:
        return None
    lo, hi = win[0][0], win[-1][1]
    inner = [sp for sp in spans if sp[0] != WINDOW_SPAN]
    busy, totals, gaps = [], {}, []
    for events in devices:
        ops = _clip([(s, s + d) for _n, s, d in events], lo, hi)
        merged = _union(ops)
        busy.append(sum(b - a for a, b in merged))
        for n, s, d in _leaves(events):
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                totals[op_name(n)] = totals.get(op_name(n), 0) + (b - a)
        edges = [lo] + [v for iv in merged for v in iv] + [hi]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    if not any(busy):
        return None
    n_dev = len(devices)

    def label(a: int, b: int) -> str:
        """The span that covers most of the gap, or ``no span`` where the
        host was in none of the benchmark's spans for longer."""
        hits = [(min(b, e) - max(a, s), n) for n, s, e in inner
                if min(b, e) > max(a, s)]
        covered = _union([(max(a, s), min(b, e)) for n, s, e in inner
                          if min(b, e) > max(a, s)])
        free = (b - a) - sum(y - x for x, y in covered)
        best = max(hits, default=(0, "no span"))
        return best[1] if best[0] >= free else "no span"

    gaps.sort(key=lambda g: g[0] - g[1])
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / n_dev / 1e9,
        "device_ops": [[n, t / n_dev / 1e9] for n, t in ops],
        "idle_gaps": [[label(a, b), (b - a) / 1e9] for a, b in gaps[:TOP]],
    }


def idle_pct(reduced: dict | None) -> float | None:
    """Share of the traced window in which no op ran on the device."""
    if not reduced:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
