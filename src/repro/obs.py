"""Spans and counters of the program, on the profiler's clock.

The program's one tracing module. Tracing is off by default; the caller
(a launcher, a benchmark, a test) switches it with ``enable()`` and
``disable()``.

- ``span(name, **attrs)`` brackets host work. Off, it returns one shared
  no-op context after reading one module global: no annotation is made,
  nothing is recorded and no clock is read; the call's keyword
  arguments are still built, so a site whose attributes cost more than
  that builds them only when ``enabled()``. On, the span is recorded in
  memory as ``(name, start_ns, end_ns, parent, attrs)``, ``parent`` being
  the name of the enclosing span on the same thread (None at the top),
  and is written into the profiler's trace as
  ``jax.profiler.TraceAnnotation("repro." + name, **attrs)``, so a
  profile shows it on the same clock as the device ops with its
  attributes (``rid=7``) as event stats. ``attrs`` only known once the
  work is done are added with the span's ``set(**attrs)``.
- ``count(name, n=1)`` adds to a counter; off, it returns at once.
- ``snapshot()`` returns what was recorded, ``reset()`` clears it. The
  record keeps the newest ``MAX_SPANS`` spans: once it is full, each new
  span pushes out the oldest and adds one to ``dropped``, so a caller
  that leaves tracing on holds a bounded record.

Device stages are named inside jitted code with ``jax.named_scope``
(``ivf.probe``, ``ivf.gather``, ``ivf.scan``, ``lloyd.assign``, ...):
metadata on the compiled ops that the profiler reports as each op's
``tf_op``, with no change to the computation.
"""
from __future__ import annotations

import collections
import threading
import time

import jax

PREFIX = "repro."
MAX_SPANS = 65_536

_on = False
_lock = threading.Lock()
_spans: collections.deque = collections.deque(maxlen=MAX_SPANS)
_dropped = 0
_counters: dict[str, int] = {}
_local = threading.local()


class _NoSpan:
    """The context ``span`` returns while tracing is off."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> None:
        pass


_NO_SPAN = _NoSpan()


class _Span:
    __slots__ = ("name", "attrs", "parent", "ann", "t0")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        stack = _stack()
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        self.ann = jax.profiler.TraceAnnotation(PREFIX + self.name,
                                                **self.attrs)
        self.ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        global _dropped
        t1 = time.perf_counter_ns()
        self.ann.__exit__(*exc)
        _stack().pop()
        with _lock:
            if len(_spans) == MAX_SPANS:
                _dropped += 1
            _spans.append((self.name, self.t0, t1, self.parent, self.attrs))
        return False

    def set(self, **attrs) -> None:
        """Add attributes known only once the span's work is done."""
        self.attrs.update(attrs)
        self.ann.set_metadata(**attrs)


def _stack() -> list[str]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def span(name: str, **attrs):
    """Context manager around host work named ``name`` (see module doc)."""
    if not _on:
        return _NO_SPAN
    return _Span(name, attrs)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` (tracing on only)."""
    if not _on:
        return
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def enable() -> None:
    global _on
    _on = True


def disable() -> None:
    global _on
    _on = False


def enabled() -> bool:
    return _on


def snapshot() -> dict:
    """``{"spans": [(name, start_ns, end_ns, parent, attrs), ...],
    "counters": {name: total}, "dropped": n}``, copies of what was
    recorded since the last ``reset``; ``dropped`` counts the spans pushed
    out of the full record, oldest first."""
    with _lock:
        return {"spans": list(_spans), "counters": dict(_counters),
                "dropped": _dropped}


def reset() -> None:
    """Forget every recorded span and counter."""
    global _dropped
    with _lock:
        _spans.clear()
        _counters.clear()
        _dropped = 0
