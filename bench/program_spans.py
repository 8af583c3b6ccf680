"""The benchmark's one door to the program's span recorder
(``repro.serve.trace``): the engine's own host spans of a finished run.

The recorder times spans on ``time.perf_counter()``, as the open loop
(``serve_loop``) times its ticks.  A profiler trace has a clock of its own, so ``offset``
maps one onto the other: the median, over the traced ticks, of a
``bench.decode`` annotation's start in the trace less the same tick's start
on ``perf_counter`` (``run._reduce_trace`` pairs them one to one).

A program without the recorder gives no spans, and the readers built on
these then report nothing."""

from __future__ import annotations

import statistics
from typing import Dict, List, Optional

from bench import serve_loop
from bench import trace as tr


def recorded(run) -> list:
    """The recorder's spans that lie inside the open loop of ``run``,
    oldest end first (``repro.serve.trace.Span``: ``name``, ``start``,
    ``end``, ``sid``, ``parent``, ``rid``, ``counts``)."""
    try:
        from repro.serve import trace
    except ImportError:
        return []
    log = run.log
    if log is None:
        return []
    return trace.spans(log.t0, log.t0 + log.end)


def in_window(run, spans, name: str) -> list:
    """Spans called ``name`` that lie inside the measured window."""
    w0, w1 = run.log.window
    lo, hi = run.log.t0 + w0, run.log.t0 + w1
    return [s for s in spans if s.name == name and lo <= s.start
            and s.end <= hi]


def child_s(spans, name: str) -> Dict[int, float]:
    """Seconds of the children called ``name``, summed by their parent."""
    out: Dict[int, float] = {}
    for s in spans:
        if s.name == name and s.parent is not None:
            out[s.parent] = out.get(s.parent, 0.0) + s.end - s.start
    return out


def self_ms(run, name: str, less: str) -> Optional[float]:
    """Mean over the window's ``name`` spans of their duration less the
    time of their ``less`` children, in milliseconds."""
    spans = recorded(run)
    outer = in_window(run, spans, name)
    if not outer:
        return None
    sub = child_s(spans, less)
    return 1e3 * sum(s.end - s.start - sub.get(s.sid, 0.0)
                     for s in outer) / len(outer)


def offset(run) -> Optional[float]:
    """Trace clock less ``perf_counter``, from the paired decode spans."""
    if run.trace is None or not run.traced_ticks:
        return None
    spans = [s for s in run.trace.spans if s.name == serve_loop.DECODE]
    d = [s.start - (run.log.t0 + t.start)
         for s, t in zip(spans, run.traced_ticks)]
    return statistics.median(d) if d else None


def mapped(run, name: str) -> List[tr.Event]:
    """The recorder's ``name`` spans on the trace's clock, those inside the
    traced window only."""
    off = offset(run)
    if off is None:
        return []
    lo, hi = run.trace.window
    out = [tr.Event(s.name, s.start + off, s.end + off)
           for s in recorded(run) if s.name == name]
    return [e for e in out if lo <= e.start and e.end <= hi]
