"""Reading a profiler trace: device operations and the harness's host spans
on one clock, and the reductions the per-layer metrics share.

``load(dir)`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote.  Device
operations are the events of each TPU plane's ``XLA Ops`` line, executables
those of its ``XLA Modules`` line; host spans are the ``bench.*``
annotations of ``serve_loop``.  Times are seconds.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]


@dataclasses.dataclass
class Event:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: List[List[Event]]          # per device: XLA ops
    modules: List[List[Event]]      # per device: executables
    spans: List[Event]              # host: bench.* annotations
    window: Interval                # first span start .. last span end

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def load(trace_dir: str, span_prefix: str = "bench.") -> Trace:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    ops, modules, spans = [], [], []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            lines = {line.name: line for line in plane.lines}
            ops.append(_events(lines.get("XLA Ops"), short))
            modules.append(_events(lines.get("XLA Modules")))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(e for e in _events(line)
                             if e.name.startswith(span_prefix))
    spans.sort(key=lambda e: e.start)
    window = ((spans[0].start, spans[-1].end) if spans else (0.0, 0.0))
    return Trace(ops=ops, modules=modules, spans=spans, window=window)


def short(name: str) -> str:
    """An HLO op as the trace prints it (``%nm_xwt.48 = bf16[128,8192]{..}
    custom-call(...)``), cut to its name and result type."""
    if " = " not in name:
        return name
    op, rest = name.split(" = ", 1)
    kind = rest.split("{", 1)[0].split("(", 1)[0].strip()
    return f"{op} {kind}" if kind else op


def _events(line, rename=lambda n: n) -> List[Event]:
    if line is None:
        return []
    out = [Event(rename(e.name), e.start_ns * 1e-9,
                 (e.start_ns + e.duration_ns) * 1e-9) for e in line.events]
    out.sort(key=lambda e: e.start)
    return out


def merge(intervals: Sequence[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Union of intervals, clipped to [lo, hi], as sorted disjoint pieces."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(events: Sequence[Event], window: Interval) -> float:
    """Seconds of the window in which at least one operation ran."""
    return sum(e - s for s, e in merge([(x.start, x.end) for x in events],
                                       *window))


def idle_gaps(events: Sequence[Event], window: Interval) -> List[Interval]:
    pieces = merge([(x.start, x.end) for x in events], *window)
    gaps, t = [], window[0]
    for s, e in pieces:
        if s > t:
            gaps.append((t, s))
        t = e
    if window[1] > t:
        gaps.append((t, window[1]))
    return gaps


class SpanIndex:
    """Which host span covers a time (spans do not nest in the harness)."""

    def __init__(self, spans: Sequence[Event]):
        self.spans = sorted(spans, key=lambda e: e.start)
        self.starts = [s.start for s in self.spans]

    def at(self, t: float) -> Optional[Event]:
        i = bisect.bisect_right(self.starts, t) - 1
        if i >= 0 and self.spans[i].end >= t:
            return self.spans[i]
        return None

    def owner(self, ev: Event) -> Optional[Event]:
        """The span an operation belongs to: the one covering its middle."""
        return self.at(0.5 * (ev.start + ev.end))


def within(events: Sequence[Event], spans: Sequence[Event], name: str,
           pattern: Optional[str] = None) -> Dict[int, List[Event]]:
    """Events (optionally those whose name matches ``pattern``) grouped by
    the index, among spans called ``name``, of the span they fall in."""
    chosen = [s for s in spans if s.name == name]
    index = SpanIndex(chosen)
    pos = {id(s): i for i, s in enumerate(index.spans)}
    rx = re.compile(pattern) if pattern else None
    out: Dict[int, List[Event]] = {}
    for ev in events:
        if rx is not None and not rx.search(ev.name):
            continue
        sp = index.owner(ev)
        if sp is not None:
            out.setdefault(pos[id(sp)], []).append(ev)
    return out


def top_ops(events: Sequence[Event], window: Interval, k: int = 10
            ) -> List[list]:
    """The ``k`` operation names that took most device time in the window."""
    tot: Dict[str, float] = {}
    for e in events:
        d = min(e.end, window[1]) - max(e.start, window[0])
        if d > 0:
            tot[e.name] = tot.get(e.name, 0.0) + d
    return [[n, s] for n, s in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def gaps_by_host(events: Sequence[Event], spans: Sequence[Event],
                 window: Interval, k: int = 10) -> List[list]:
    """Device idle time in the window, summed by what the host was doing
    (the host span covering each gap's middle; ``host`` outside any span),
    each entry named with its gap count and its longest gap."""
    index = SpanIndex(spans)
    tot: Dict[str, List[float]] = {}
    for s, e in idle_gaps(events, window):
        sp = index.at(0.5 * (s + e))
        acc = tot.setdefault(sp.name if sp else "host", [0.0, 0, 0.0])
        acc[0] += e - s
        acc[1] += 1
        acc[2] = max(acc[2], e - s)
    rows = sorted(tot.items(), key=lambda x: -x[1][0])[:k]
    return [[f"{n} ({c} gaps, longest {m * 1e3:.3f} ms)", t]
            for n, (t, c, m) in rows]


def pair(spans: Sequence[Event], ticks: Sequence) -> Tuple[list, list]:
    """Pair traced decode spans with the driver's ticks (``start``/``end``
    on the host clock), in order.  Where the counts differ, the profiler
    lost spans at an edge: keep the alignment, first or last, whose
    durations agree best."""
    n = min(len(spans), len(ticks))
    if len(spans) == len(ticks) or n == 0:
        return list(spans[:n]), list(ticks[:n])

    def err(a, b):
        return sum(abs(x.dur - (y.end - y.start)) for x, y in zip(a, b))
    head = (list(spans[:n]), list(ticks[:n]))
    tail = (list(spans[-n:]), list(ticks[-n:]))
    return head if err(*head) <= err(*tail) else tail
