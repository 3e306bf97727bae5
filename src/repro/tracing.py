"""Spans of the program's host-side phases, on the profiler's clock.

The program's one timing mechanism.  ``span(name, **attrs)`` times a block:
it emits ``jax.profiler.TraceAnnotation(name)`` around it, so a profiler
trace shows the program's phases beside the device planes, and on exit
appends a ``Record`` to a bounded in-memory ring that ``recorded()`` returns::

    with tracing.span("study.run") as s:
        ...
        s.count("host_syncs")

A record holds its parent (the innermost open span of the thread, or an
explicit ``parent=``: the span that caused it, which for a later stage of a
query may have ended before it starts), the id of its root (shared by every
span of one study or one query), its attributes and its counters.  Stamps are
``time.time_ns()``: CLOCK_REALTIME, the clock the profiler stamps its host
events with, so a record lines up with its annotation in a trace once the
trace's own origin is removed.

Recording is always on, and stays cheap because spans are coarse: only at
host-side phase boundaries, never inside a jitted or traced body, never per
row, per plan node or per step of a loop.  ``begin``/``Span.end`` time an
interval that is not a block (a query waiting in the queue); such a span has
no annotation, since the profiler's annotations nest per thread.

This is not ``OperationLog``: the log is a study's provenance and is
serialised with its results; these records are timings, kept only in memory.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from typing import Any, Dict, Iterator, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

__all__ = ["Record", "Span", "span", "begin", "recorded"]

#: records kept; the oldest fall out first (a study records about a dozen)
RING_SIZE = 4096


class Record(NamedTuple):
    id: int
    parent_id: Optional[int]
    root_id: int
    name: str
    start_ns: int
    end_ns: int
    attrs: Dict[str, Any]
    counts: Dict[str, int]

    @property
    def wall_ns(self) -> int:
        return self.end_ns - self.start_ns


_ring: "collections.deque[Record]" = collections.deque(maxlen=RING_SIZE)
_ids = itertools.count(1)
_local = threading.local()


def _stack() -> List["Span"]:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


class Span:
    """An open span; ``end()`` records it (once)."""

    __slots__ = ("id", "parent_id", "root_id", "name", "start_ns", "end_ns",
                 "attrs", "counts")

    def __init__(self, name: str, parent: Optional["Span"], attrs: Dict):
        self.id = next(_ids)
        self.parent_id = parent.id if parent is not None else None
        self.root_id = parent.root_id if parent is not None else self.id
        self.name = name
        self.attrs = attrs
        self.counts: Dict[str, int] = {}
        self.end_ns: Optional[int] = None
        self.start_ns = time.time_ns()

    def count(self, key: str, n: int = 1) -> None:
        """Add ``n`` to this span's counter ``key``."""
        self.counts[key] = self.counts.get(key, 0) + int(n)

    @property
    def seconds(self) -> float:
        """The wall of an ended span."""
        return (self.end_ns - self.start_ns) * 1e-9

    def end(self) -> None:
        if self.end_ns is None:
            self.end_ns = time.time_ns()
            self._keep()

    def _keep(self) -> None:
        _ring.append(Record(self.id, self.parent_id, self.root_id, self.name,
                            self.start_ns, self.end_ns, self.attrs,
                            self.counts))


def begin(name: str, /, parent: Optional[Span] = None, **attrs) -> Span:
    """Open a span that is not a block: it is on no thread's stack and has
    no annotation; ``end()`` records it.  Without ``parent`` it is a root."""
    return Span(name, parent, attrs)


@contextlib.contextmanager
def span(name: str, /, parent: Optional[Span] = None,
         **attrs) -> Iterator[Span]:
    """Time the block as span ``name``, annotated in any profiler trace.
    Its parent is ``parent`` (a span opened on another thread, say), else
    the innermost span open on this thread; the spans the block opens are
    its children."""
    st = _stack()
    ann = TraceAnnotation(name)
    ann.__enter__()
    # stamped just inside the annotation, recorded once it has closed
    s = Span(name, parent if parent is not None else (st[-1] if st else None),
             attrs)
    st.append(s)
    try:
        yield s
    finally:
        st.pop()
        s.end_ns = time.time_ns()
        ann.__exit__(None, None, None)
        s._keep()


def recorded() -> List[Record]:
    """The ring's records, oldest first (in the order the spans ended)."""
    return list(_ring)
