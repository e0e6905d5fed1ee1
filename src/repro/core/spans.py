"""Host spans of the session's work, on the clock the profiler shares.

``span(name, **attrs)`` times a stretch of host work where it happens —
inside a scheduler tick, an ingest, an adapt — and records
``(id, parent, name, start, end, attrs)`` into a bounded in-memory ring.
Times are ``time.perf_counter()``, the clock of ``Request.submitted_at``
and its siblings, so a reader can set spans against request stamps
directly. Parents come from a per-thread stack of open spans.

Each span also runs as ``jax.profiler.TraceAnnotation("repro.<name>",
**attrs)``: under a running profiler it lands on the trace's host plane,
on the same clock as the device's events, so an idle stretch of the
device can be named by the host span over it. With no profiler running
the annotation costs about a microsecond. Recording is always on.

The ring holds the newest ``RING`` records. When it is full the oldest
record goes, ``dropped`` counts it, and ``lost_until`` keeps the latest
start of any record that went: a reader over a window that starts at or
before ``lost_until`` knows its spans are incomplete.

Compiles are marked here too: ``jit_named(name, fn)`` jits ``fn`` as
``name`` (the program's module reads ``jit_<name>``), and every trace of
it counts in ``TRACE_COUNTS[name]`` and leaves a zero-length
``compile.<name>`` record in the ring.
"""

from __future__ import annotations

import functools
import itertools
import threading
import time
from collections import Counter, deque
from typing import Any, NamedTuple

import jax

#: Records the ring holds: a 51-s window of the busiest session (about 400
#: scheduler ticks of at most nine spans, or 35 fine-tune cohorts of about
#: 60) writes under 4,000, so the ring keeps over ten windows.
RING = 1 << 16

PREFIX = "repro."


class Record(NamedTuple):
    id: int
    parent: int            # 0: no parent
    name: str
    start: float           # time.perf_counter()
    end: float
    attrs: dict


class Span:
    """One stretch of host work. Use as a context manager, or ``open()``
    it and ``close()`` it later: closing twice is harmless, and closing a
    span first closes any span still open inside it."""

    __slots__ = ("rec", "name", "attrs", "id", "parent", "start", "_ann",
                 "_open")

    def __init__(self, rec: "Recorder", name: str, attrs: dict):
        self.rec, self.name, self.attrs = rec, name, attrs
        self.id = self.parent = 0
        self.start = 0.0
        self._ann = None
        self._open = False

    def open(self) -> "Span":
        stack = self.rec._stack()
        self.id = next(self.rec._ids)
        self.parent = stack[-1].id if stack else 0
        stack.append(self)
        self._ann = jax.profiler.TraceAnnotation(PREFIX + self.name,
                                                 **self.attrs)
        self._ann.__enter__()
        self._open = True
        self.start = time.perf_counter()
        return self

    def set(self, **attrs) -> None:
        """Add attributes known only once the work has run (they reach the
        profiler too while it records)."""
        self.attrs.update(attrs)
        if self._open and jax.profiler.TraceAnnotation.is_enabled():
            self._ann.set_metadata(**attrs)

    def close(self) -> None:
        if not self._open:
            return
        stack = self.rec._stack()
        if self in stack:
            # Spans an exception left open inside this one close with it.
            while stack[-1] is not self:
                stack[-1].close()
            stack.pop()
        end = time.perf_counter()
        self._open = False
        self._ann.__exit__(None, None, None)
        self.rec._append(Record(self.id, self.parent, self.name, self.start,
                                end, self.attrs))

    def __enter__(self) -> "Span":
        return self.open()

    def __exit__(self, *exc) -> None:
        self.close()


class Recorder:
    """The bounded ring of finished spans and marks."""

    def __init__(self, maxlen: int = RING):
        self.ring: deque[Record] = deque(maxlen=maxlen)
        self.dropped = 0
        self.lost_until = float("-inf")
        self._ids = itertools.count(1)
        self._local = threading.local()

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _append(self, rec: Record) -> None:
        ring = self.ring
        if len(ring) == ring.maxlen:
            self.dropped += 1
            self.lost_until = max(self.lost_until, ring[0].start)
        ring.append(rec)

    def span(self, name: str, **attrs) -> Span:
        return Span(self, name, attrs)

    def mark(self, name: str) -> None:
        """A zero-length record under the innermost open span."""
        stack = self._stack()
        now = time.perf_counter()
        self._append(Record(next(self._ids), stack[-1].id if stack else 0,
                            name, now, now, {}))

    def records(self) -> list[Record]:
        return list(self.ring)


RECORDER = Recorder()
span = RECORDER.span

#: Trace counter: ``mark_trace(name)`` runs as a Python side effect inside
#: a jitted body, so it fires once per trace (the first call and every
#: shape or static-argument retrace) and never on a cache hit. Tests
#: assert, e.g., that serving new temperatures leaves it unchanged.
TRACE_COUNTS: Counter = Counter()


def mark_trace(name: str) -> None:
    TRACE_COUNTS[name] += 1
    RECORDER.mark("compile." + name)


def jit_named(name: str, fn, **jit_kw) -> Any:
    """``jax.jit(fn, **jit_kw)`` under ``name``, every trace marked."""

    @functools.wraps(fn)
    def body(*args, **kw):
        mark_trace(name)
        return fn(*args, **kw)

    body.__name__ = body.__qualname__ = name
    return jax.jit(body, **jit_kw)

