"""Host spans of the serving engine, kept in memory.

Each phase of the engine's loop opens a span: a decode tick (``serve.step``)
and its parts, an admission (``serve.admit``) and its parts.  A span holds
its name, its start and end on ``time.perf_counter()``, the span it sits
in, the engine that emitted it, the request it serves and integer counts of
the work it did.  The same ``with`` opens a ``jax.profiler.TraceAnnotation``
of the same name, so in a profiler trace the engine's phases sit on the
device trace's own clock, beside the device work they dispatched
(TensorBoard, Perfetto).

A request span that opens in one call and closes in another (``serve.replay``,
from admission to the request's first token) goes through ``record``, to
the recorder only: annotations in a profiler trace must nest.

Spans are kept in one ring per process, ``CAPACITY`` spans long, always on.
When it is full the oldest span is dropped and counted (``dropped()``).
``spans(t0, t1)`` reads it, ``reset()`` empties it.
"""

from __future__ import annotations

import collections
import itertools
import math
import threading
import time
from typing import Deque, Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

CAPACITY = 2 ** 18


class Span(NamedTuple):
    name: str
    start: float                    # time.perf_counter()
    end: float
    sid: int                        # serial number, unique in the process
    parent: Optional[int]           # sid of the enclosing span
    engine: int                     # id of the emitting engine
    rid: Optional[int]              # request served, where there is one
    counts: Dict[str, int]

    @property
    def dur(self) -> float:
        return self.end - self.start


class _Ring:
    def __init__(self, capacity: int):
        self.spans: Deque[Span] = collections.deque(maxlen=capacity)
        self.dropped = 0
        self.lock = threading.Lock()

    def add(self, span: Span) -> None:
        with self.lock:
            if len(self.spans) == self.spans.maxlen:
                self.dropped += 1
            self.spans.append(span)


_ring = _Ring(CAPACITY)
_serial = itertools.count(1)
_engines = itertools.count(1)
_open = threading.local()           # per thread: the stack of open spans


def _stack() -> list:
    try:
        return _open.stack
    except AttributeError:
        _open.stack = []
        return _open.stack


def new_engine() -> int:
    """A fresh engine id: spans of two engines in one process stay apart."""
    return next(_engines)


class _Open:
    """An open span: the context manager ``span`` returns.  ``counts`` may
    be updated until it closes; ``start``/``end`` are set on entry/exit."""

    __slots__ = ("name", "engine", "rid", "counts", "sid", "parent",
                 "start", "end", "_note")

    def __init__(self, name, engine, rid, counts):
        self.name, self.engine, self.rid, self.counts = (name, engine, rid,
                                                         counts)
        self.sid = next(_serial)
        self.start = self.end = math.nan

    def __enter__(self) -> "_Open":
        stack = _stack()
        outer = stack[-1] if stack else None
        self.parent = outer.sid if outer else None
        if self.engine is None:
            self.engine = outer.engine if outer else 0
        stack.append(self)
        self._note = TraceAnnotation(self.name)
        self._note.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        self._note.__exit__(*exc)
        _stack().pop()
        _ring.add(Span(self.name, self.start, self.end, self.sid, self.parent,
                       self.engine, self.rid, self.counts))


def span(name: str, *, engine: Optional[int] = None,
         rid: Optional[int] = None, **counts: int) -> _Open:
    """``with span("serve.step", engine=e) as sp:`` records one span of the
    body.  ``engine`` defaults to the enclosing span's."""
    return _Open(name, engine, rid, counts)


def record(name: str, start: float, end: float, *, engine: int,
           rid: Optional[int] = None, **counts: int) -> None:
    """Record a span timed by the caller (no profiler annotation)."""
    _ring.add(Span(name, start, end, next(_serial), None, engine, rid,
                   counts))


def spans(t0: float = -math.inf, t1: float = math.inf, *,
          engine: Optional[int] = None, name: Optional[str] = None
          ) -> List[Span]:
    """Spans that lie wholly inside ``[t0, t1]``, oldest end first,
    optionally of one engine and one name."""
    with _ring.lock:
        held = list(_ring.spans)
    return [s for s in held if t0 <= s.start and s.end <= t1
            and (engine is None or s.engine == engine)
            and (name is None or s.name == name)]


def dropped() -> int:
    """Spans dropped from the full ring since the last ``reset``."""
    return _ring.dropped


def reset() -> None:
    """Empty the ring (sized to ``CAPACITY`` as it reads now)."""
    global _ring
    _ring = _Ring(CAPACITY)
