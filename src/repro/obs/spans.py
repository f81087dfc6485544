"""Host spans of the serving engine, on the device trace's clock.

``SpanLog.span(name, rid=None, **meta)`` is a context manager. It opens
``jax.profiler.TraceAnnotation(name, **meta)``, so that while the
profiler runs the span is a host event of its ``.xplane.pb``, on the
clock the device's events are put on. On exit it appends one record
``Span(id, parent, name, t0_ns, t1_ns, rid, meta)`` to a bounded deque:
``parent`` is the id of the span it opened inside, ``rid`` the request it
belongs to. Times are ``time.time_ns()``, read once at each boundary,
inside the annotation; the open span keeps them (``t0_ns``, ``t1_ns``) so
the caller stamps from the same reads.

A trace's event times count from the profile's start, the
``profile_start_time`` stat of its ``Task Environment`` plane: a record's
time is that plus the event's ``start_ns``.

The record is always on and holds the last ``MAX_SPANS`` spans, so a
server that runs for days holds no more. A span times the host only: it
never waits for the device; device time comes from the trace. The spans
of one log nest by a stack, so one thread opens them.
"""
from __future__ import annotations

import itertools
import time
from collections import deque
from typing import Any, Deque, Dict, List, NamedTuple, Optional

MAX_SPANS = 1 << 14


class Span(NamedTuple):
    id: int
    parent: Optional[int]
    name: str
    t0_ns: int
    t1_ns: int
    rid: Optional[int]
    meta: Dict[str, Any]


class SpanLog:
    """The finished spans, oldest first; past ``MAX_SPANS`` the oldest go."""

    def __init__(self):
        from jax.profiler import TraceAnnotation
        self._annotation = TraceAnnotation
        self.records: Deque[Span] = deque(maxlen=MAX_SPANS)
        self._open: List[int] = []
        self._ids = itertools.count()

    def span(self, name: str, rid: Optional[int] = None,
             **meta) -> "OpenSpan":
        return OpenSpan(self, name, rid, meta)


class OpenSpan:
    __slots__ = ("log", "name", "rid", "meta", "id", "parent", "t0_ns",
                 "t1_ns", "_ann")

    def __init__(self, log: SpanLog, name: str, rid: Optional[int],
                 meta: Dict[str, Any]):
        self.log, self.name, self.rid, self.meta = log, name, rid, meta

    def __enter__(self) -> "OpenSpan":
        log = self.log
        self.id = next(log._ids)
        self.parent = log._open[-1] if log._open else None
        log._open.append(self.id)
        args = self.meta if self.rid is None else dict(self.meta, rid=self.rid)
        self._ann = log._annotation(self.name, **args)
        self._ann.__enter__()
        self.t0_ns = time.time_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.t1_ns = time.time_ns()
        self._ann.__exit__(*exc)
        self.log._open.pop()
        self.log.records.append(Span(self.id, self.parent, self.name,
                                     self.t0_ns, self.t1_ns, self.rid,
                                     self.meta))
