"""In-program telemetry: host spans, instant marks and counters on the
profiler's clock.

One process-wide :class:`Recorder` (``recorder()``) keeps every span the
served path opens in a bounded ring, on ``time.perf_counter()``. Each
``span`` also opens a ``jax.profiler.TraceAnnotation`` of the same name,
so under a profile it sits on the host plane beside the device's ops,
on the device trace's clock. Recording is always on; it costs a few
microseconds per span (DESIGN.md §3, "Telemetry").

    from repro.serving.telemetry import recorder, span

    with span("engine.step", step=7) as sp:
        ...
        sp.attrs["rows"] = 16        # attrs may be filled in the body
    recorder().spans("engine.step", lo=t_open, hi=t_close)

A span record is ``(id, parent, name, t0, t1, attrs)``; ``parent`` is the
id of the span open around it (0 at the top). A mark is a record with
``t0 == t1``. The ring holds ``CAPACITY`` records; when it overflows the
oldest go and ``dropped`` counts them. The recorder is not the engine's:
it outlives every engine, so a reader may look after the engine is gone.

One ``jax.monitoring`` listener, registered with the recorder, turns each
XLA compile (or load from the persistent cache) into a ``jax.compile``
span (attr ``fun``) nested under the span that caused it, and keeps the
totals ``jax_compiles`` and ``jax_compile_s``.

Spans nest by a single stack, so they are meant to be opened from one
thread, the one that steps the engine.
"""
from __future__ import annotations

import time
from typing import List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

CAPACITY = 1 << 17     # records: over 200 s of steps at 17 steps/s, 30 spans each
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_clock = time.perf_counter


class SpanRecord(NamedTuple):
    id: int
    parent: int
    name: str
    t0: float
    t1: float
    attrs: dict


class Span:
    """An open span; ``attrs`` may be filled in before it closes."""

    __slots__ = ("_rec", "name", "attrs", "id", "parent", "t0", "_ann")

    def __init__(self, rec: "Recorder", name: str, attrs: dict):
        self._rec = rec
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "Span":
        rec = self._rec
        rec._next_id += 1
        self.id = rec._next_id
        stack = rec._stack
        self.parent = stack[-1] if stack else 0
        stack.append(self.id)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = _clock()
        return self

    def __exit__(self, *exc) -> None:
        t1 = _clock()
        self._ann.__exit__(None, None, None)
        rec = self._rec
        rec._stack.pop()
        rec._append(self.id, self.parent, self.name, self.t0, t1,
                    self.attrs)


class Recorder:
    """The bounded ring of span and mark records, and the compile totals.

    The ring is six preallocated columns rather than a list of tuples: a
    record then holds nothing the garbage collector tracks (floats, ints,
    names and attrs of plain values), so a full ring does not make the
    collector's full passes longer or more frequent."""

    def __init__(self, capacity: int = CAPACITY):
        self.capacity = capacity
        self._cols = tuple([None] * capacity for _ in SpanRecord._fields)
        self._n = 0                      # records ever written
        self._stack: List[int] = []
        self._next_id = 0
        self.dropped = 0
        self._dropped_t1 = float("-inf")   # end of the newest lost record
        self.jax_compiles = 0
        self.jax_compile_s = 0.0

    # -- recording -----------------------------------------------------
    def _append(self, id_: int, parent: int, name: str, t0: float,
                t1: float, attrs: dict) -> None:
        n = self._n
        k = n % self.capacity
        ids, parents, names, t0s, t1s, attrss = self._cols
        if n >= self.capacity:
            self.dropped += 1
            self._dropped_t1 = t1s[k]
        ids[k] = id_
        parents[k] = parent
        names[k] = name
        t0s[k] = t0
        t1s[k] = t1
        attrss[k] = attrs
        self._n = n + 1

    def span(self, name: str, **attrs) -> Span:
        return Span(self, name, attrs)

    def mark(self, name: str, **attrs) -> float:
        """An instant event at now, under the open span; returns now."""
        with TraceAnnotation(name):
            t = _clock()
        self.record(name, t, t, **attrs)
        return t

    def record(self, name: str, t0: float, t1: float, **attrs) -> None:
        """A span that opened and closed at given times, under the open
        span. It lies only in memory: a profile cannot hold an interval
        that started before its annotation could."""
        self._next_id += 1
        self._append(self._next_id, self._stack[-1] if self._stack else 0,
                     name, t0, t1, attrs)

    # -- reading -------------------------------------------------------
    def spans(self, name: Optional[str] = None, lo: Optional[float] = None,
              hi: Optional[float] = None) -> List[SpanRecord]:
        """Records (``name`` alone, if given) whose [t0, t1] lies in
        [lo, hi], oldest close first."""
        lo = float("-inf") if lo is None else lo
        hi = float("inf") if hi is None else hi
        first = max(0, self._n - self.capacity)
        cols = self._cols
        out = []
        for n in range(first, self._n):
            k = n % self.capacity
            if ((name is None or cols[2][k] == name)
                    and lo <= cols[3][k] and cols[4][k] <= hi):
                out.append(SpanRecord(*(c[k] for c in cols)))
        return out

    def complete_since(self, t: float) -> bool:
        """True when no record that closed at or after ``t`` was lost to
        the ring (records enter it as they close, oldest first out)."""
        return self._dropped_t1 < t

    def totals(self) -> dict:
        return {"spans_dropped": self.dropped,
                "jax_compiles": self.jax_compiles,
                "jax_compile_s": self.jax_compile_s}

    # -- jax compile listener -----------------------------------------
    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event != COMPILE_EVENT:
            return
        t1 = _clock()
        self.jax_compiles += 1
        self.jax_compile_s += duration
        self.record("jax.compile", t1 - duration, t1,
                    fun=str(kw.get("fun_name", "")))


_RECORDER: Optional[Recorder] = None


def recorder() -> Recorder:
    """The process-wide recorder (created, and its compile listener
    registered, on first use)."""
    global _RECORDER
    if _RECORDER is None:
        import jax

        _RECORDER = Recorder()
        jax.monitoring.register_event_duration_secs_listener(
            _RECORDER._on_duration)
    return _RECORDER


def span(name: str, **attrs) -> Span:
    """``recorder().span``: a context manager recording one span."""
    return Span(_RECORDER or recorder(), name, attrs)


def mark(name: str, **attrs) -> float:
    return (_RECORDER or recorder()).mark(name, **attrs)
