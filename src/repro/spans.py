"""Program spans on the profiler's clock.

``span(name, **meta)`` marks one step of the program's host work (a
service flush, a plan, a sweep's enqueue). It records only while a
``jax.profiler`` session is on, and then in two places:

* the profiler's host plane, as a ``TraceAnnotation`` with ``meta`` as
  its stats, on the same clock as the device ops, so that a trace
  viewer or a trace reduction can charge the device's idle gaps to the
  span open at the time;
* aggregates in this process, per span name: ``count``, ``total_ns``,
  ``self_ns`` (the duration less that of the direct child spans) and
  ``max_ns``, which ``snapshot()`` copies out.

With the profiler off a span is one check and returns a shared null
context whose ``set`` does nothing: no object built, no clock read. A
``meta`` value that is callable is called only when the span records,
so a value dear to build (the request ids of a batch) costs nothing
untraced; ``set(**meta)`` adds stats to a span already open.

The aggregates hold the latest profiler session: the first span
recorded while the profiler is on, after any span met while it was
off, clears them. So a program that runs its set-up untraced and then
traces a window reads exactly the window's spans after the trace stops.

An operator starts a profiler trace (``jax.profiler.start_trace``, or
``jax.profiler.trace``), runs the work, and then reads ``snapshot()``,
or opens the trace in a viewer.
"""
from __future__ import annotations

import threading
import time

from jax.profiler import TraceAnnotation


class _Null:
    """The shared span of a process with the profiler off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **meta):
        pass


_NULL = _Null()
_is_enabled = TraceAnnotation.is_enabled
_clock = time.perf_counter_ns

_lock = threading.Lock()
_local = threading.local()
# name -> [count, total_ns, self_ns, max_ns]
_totals: dict = {}
# A span was met with the profiler off since the aggregates were last
# cleared: the next span recorded starts a new session.
_off_since_clear = False


def _resolved(meta: dict) -> dict:
    return {k: v() if callable(v) else v for k, v in meta.items()}


class _Span:
    __slots__ = ("name", "meta", "annotation", "t0", "child_ns")

    def __init__(self, name: str, meta: dict):
        self.name = name
        self.meta = meta

    def __enter__(self):
        global _off_since_clear
        if _off_since_clear:
            with _lock:
                _totals.clear()
                _off_since_clear = False
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        stack.append(self)
        self.child_ns = 0
        self.annotation = TraceAnnotation(self.name,
                                          **_resolved(self.meta))
        self.annotation.__enter__()
        self.t0 = _clock()
        return self

    def set(self, **meta):
        """Add stats to the open span's trace event."""
        self.annotation.set_metadata(**_resolved(meta))

    def __exit__(self, *exc):
        dur = _clock() - self.t0
        self.annotation.__exit__(*exc)
        stack = _local.stack
        stack.pop()
        if stack:
            stack[-1].child_ns += dur
        with _lock:
            agg = _totals.get(self.name)
            if agg is None:
                agg = _totals[self.name] = [0, 0, 0, 0]
            agg[0] += 1
            agg[1] += dur
            agg[2] += dur - self.child_ns
            agg[3] = max(agg[3], dur)
        return False


def span(name: str, **meta):
    """A context manager that records ``name`` while the profiler is on
    (module docstring); ``meta`` goes to the trace event's stats."""
    if not _is_enabled():
        global _off_since_clear
        _off_since_clear = True
        return _NULL
    return _Span(name, meta)


def snapshot() -> dict:
    """The latest profiler session's aggregates: ``{name: {"count",
    "total_ns", "self_ns", "max_ns"}}``, a copy."""
    with _lock:
        return {name: {"count": c, "total_ns": t, "self_ns": s,
                       "max_ns": m}
                for name, (c, t, s, m) in _totals.items()}


def reset() -> None:
    """Clear the aggregates."""
    global _off_since_clear
    with _lock:
        _totals.clear()
        _off_since_clear = False
