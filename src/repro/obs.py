"""Spans and counters for the program's own layers, on the profiler's clock.

One registry per process.  ``span(name)`` times a block of host code twice
over: it opens a ``jax.profiler.TraceAnnotation("repro." + name)``, so under
a profiler session (``jax.profiler.start_trace``) the span lies on the host
plane beside the device operations, and it always appends a record to a
bounded in-memory log that ``snapshot()`` returns as plain Python data::

    >>> from repro import obs
    >>> obs.reset()
    >>> with obs.span("demo.outer", beat=3) as outer:
    ...     with obs.span("demo.inner"):
    ...         pass
    ...     outer.set(rows=64)
    >>> inner, outer = obs.snapshot()["spans"]
    >>> (inner["name"], inner["beat"], inner["parent"] == outer["id"])
    ('demo.inner', 3, True)
    >>> outer["attrs"], obs.snapshot()["aggregates"]["demo.outer"]["count"]
    ({'rows': 64}, 1)

Each record says what else happened while it was open: the jaxpr traces and
backend compiles that JAX reported (``jax.monitoring``, per function name),
and the milliseconds the Python garbage collector ran.  Those tallies are
process-wide, so a span counts a compile or a collection in another thread
that overlaps it.  A span's ``beat`` ties the records of one service beat
together; a span given none takes its parent's.

The registry hooks itself into JAX and ``gc`` on the first span, or where a
program calls ``hook()`` before its first trace, never at import.  There is
no switch: the in-memory part costs a few microseconds a span and stays on.
"""
from __future__ import annotations

import collections
import gc
import itertools
import threading
import time
from typing import Optional

import jax

PREFIX = "repro."
LOG_SIZE = 8192  # recent span records kept; the oldest go first

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
_local = threading.local()
_ids = itertools.count(1)
_log: collections.deque = collections.deque(maxlen=LOG_SIZE)
_aggregates: dict = {}  # name -> [count, total_ns, max_ns]
_counters: collections.Counter = collections.Counter()
_traces: collections.Counter = collections.Counter()  # fun_name -> traces
_compiles: collections.Counter = collections.Counter()  # fun_name -> compiles
# Process-lifetime tallies that open spans difference: traces, compiles, and
# nanoseconds of garbage collection.  Never reset.
_totals = [0, 0, 0]
_gc_start = [0]
_hooked = False
_annotation = jax.profiler.TraceAnnotation
_now = time.perf_counter_ns


def _on_jax_event(event: str, duration: float, **kw) -> None:
    if event == _TRACE_EVENT:
        tally, slot = _traces, 0
    elif event == _COMPILE_EVENT:
        tally, slot = _compiles, 1
    else:
        return
    with _lock:
        tally[kw.get("fun_name", "?")] += 1
        _totals[slot] += 1


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        _gc_start[0] = time.perf_counter_ns()
    else:
        _totals[2] += time.perf_counter_ns() - _gc_start[0]


def hook() -> None:
    """Listen to JAX's compile events and the collector, once.

    The first span does this itself; a program that opens no span calls it
    before its first trace, so that trace is counted.
    """
    global _hooked
    with _lock:
        if _hooked:
            return
        jax.monitoring.register_event_duration_secs_listener(_on_jax_event)
        gc.callbacks.append(_on_gc)
        _hooked = True


def _stack() -> list:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


class span:
    """Time a block of host code as ``name``; see the module docstring.

    ``beat`` ties the record to one beat of a loop (a span given none takes
    its parent's); ``attrs`` are stored on the record, and ``set`` adds more
    while the span is open.
    """

    __slots__ = ("name", "beat", "attrs", "id", "_parent", "_ann", "_marks",
                 "_t0")

    def __init__(self, name: str, beat: Optional[int] = None, **attrs):
        self.name = name
        self.beat = beat
        self.attrs = attrs

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self) -> "span":
        if not _hooked:
            hook()
        stack = _stack()
        if stack:
            parent = stack[-1]
            self._parent = parent.id
            if self.beat is None:
                self.beat = parent.beat
        else:
            self._parent = None
        self.id = next(_ids)
        stack.append(self)
        self._ann = ann = _annotation(PREFIX + self.name)
        ann.__enter__()
        self._marks = _totals[:]
        self._t0 = _now()
        return self

    def __exit__(self, *exc) -> None:
        t1 = _now()
        self._ann.__exit__(*exc)
        _local.stack.pop()
        dur = t1 - self._t0
        m = self._marks
        rec = (self.id, self._parent, self.name, self.beat, self._t0, dur,
               _totals[0] - m[0], _totals[1] - m[1], _totals[2] - m[2],
               self.attrs)
        _lock.acquire()
        try:
            _log.append(rec)
            agg = _aggregates.get(self.name)
            if agg is None:
                _aggregates[self.name] = [1, dur, dur]
            else:
                agg[0] += 1
                agg[1] += dur
                if dur > agg[2]:
                    agg[2] = dur
        finally:
            _lock.release()


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the host counter ``name``."""
    with _lock:
        _counters[name] += n


def snapshot() -> dict:
    """The registry as plain Python data.

    ``spans``: the recent records, oldest first, each a dict of ``id``,
    ``parent`` (the enclosing span's id or None), ``name``, ``beat``,
    ``start_ns`` and ``duration_ns`` (``time.perf_counter_ns``), ``traces``,
    ``compiles`` and ``gc_ms`` that fell inside it, and ``attrs``;
    ``aggregates``: per name over every span since the last ``reset``,
    ``count``, ``total_ms`` and ``max_ms``; ``counters``; ``traces`` and
    ``compiles``: per function name.
    """
    with _lock:
        log = list(_log)
        aggregates = {k: {"count": c, "total_ms": t * 1e-6, "max_ms": mx * 1e-6}
                      for k, (c, t, mx) in _aggregates.items()}
        counters = dict(_counters)
        traces, compiles = dict(_traces), dict(_compiles)
    keys = ("id", "parent", "name", "beat", "start_ns", "duration_ns",
            "traces", "compiles", "gc_ms", "attrs")
    spans = []
    for rec in log:
        d = dict(zip(keys, rec))
        d["gc_ms"] *= 1e-6
        d["attrs"] = dict(d["attrs"])
        spans.append(d)
    return {"spans": spans, "aggregates": aggregates, "counters": counters,
            "traces": traces, "compiles": compiles}


def reset() -> None:
    """Clear the log, the aggregates, the counters and the trace tallies."""
    with _lock:
        _log.clear()
        _aggregates.clear()
        _counters.clear()
        _traces.clear()
        _compiles.clear()
