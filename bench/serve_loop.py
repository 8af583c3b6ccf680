"""Open-loop driver of the serving engine, timed on the host clock.

The engine's own ``run()`` is a closed loop on ticks, so the benchmark
drives it one tick at a time as ``run()`` does: submit the requests that
are due, admit one at a time while the block pool fits them, then run one
batched decode tick.  Both admission and ``step`` read their results back
to the host, so a token's timestamp is when the host could first send it.

Every admission and every tick sits in a ``jax.profiler.TraceAnnotation``
(``bench.admit``, ``bench.decode``, ``bench.wait``), the host spans that
the trace reduction attributes device time and idle gaps to.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, Dict, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

from bench import program

ADMIT, DECODE, WAIT = "bench.admit", "bench.decode", "bench.wait"


@dataclasses.dataclass
class Rec:
    """One request as the client sees it (times in seconds from the loop's
    start; ``arrival`` is when it was due, not when it was submitted)."""
    rid: int
    arrival: float
    prompt_len: int
    out_len: int
    submitted: float = math.nan
    admitted: float = math.nan
    prefill_len: int = 0            # bucket the admission prefilled
    tokens: List[float] = dataclasses.field(default_factory=list)
    replay_ticks: int = 0           # decode ticks before the first token
    failed: str = ""

    @property
    def done(self) -> bool:
        return bool(self.failed) or len(self.tokens) >= self.out_len


@dataclasses.dataclass
class Tick:
    start: float
    end: float
    kv_lens: np.ndarray             # per live row, this tick's token included


@dataclasses.dataclass
class Admission:
    start: float
    end: float
    rid: int
    prefill_len: int


@dataclasses.dataclass
class Log:
    recs: Dict[int, Rec]
    ticks: List[Tick]
    admissions: List[Admission]
    t0: float                       # perf_counter of the loop's start
    window: tuple                   # (open, close), seconds from t0
    end: float                      # when the loop stopped

    def in_window(self) -> List[Rec]:
        w0, w1 = self.window
        return [r for r in self.recs.values()
                if not math.isnan(r.submitted) and w0 <= r.arrival < w1]


class OpenLoop:
    """Drive ``eng`` with ``traffic`` (``bench.traffic.Traffic``).

    ``window`` is (open, close) in seconds from the loop's start.  The loop
    runs until every request that arrived inside the window has finished,
    still offering load meanwhile, or until ``drain_limit`` seconds after
    the window closed, when what is left counts as failed.  ``at`` maps a
    time (seconds from the start) to a callable run once when the loop
    passes it: the window's open and close, the trace's start and stop."""

    def __init__(self, eng, traffic, window, drain_limit: float = 240.0,
                 at: Optional[Dict[float, Callable[[], None]]] = None,
                 clock=time.perf_counter):
        self.eng = eng
        self.traffic = traffic
        self.window = window
        self.drain_limit = drain_limit
        self.at = sorted((at or {}).items())
        self.clock = clock

    def run(self) -> Log:
        eng, tr = self.eng, self.traffic
        sched = tr.arrivals
        recs = {a.rid: Rec(a.rid, a.t, a.prompt_len, a.out_len) for a in sched}
        ticks: List[Tick] = []
        admissions: List[Admission] = []
        seen: Dict[int, int] = {}       # rid -> tokens delivered so far
        live: Dict[int, Rec] = {}       # submitted, not finished
        w0, w1 = self.window
        t0 = self.clock()
        log = Log(recs, ticks, admissions, t0, self.window, 0.0)
        pending_hooks = list(self.at)
        nxt = 0                         # next schedule entry to submit
        tick = 0

        def now() -> float:
            return self.clock() - t0

        def deliver(rid: int, n: int, t: float) -> None:
            rec = recs[rid]
            while seen.get(rid, 0) < n:
                rec.tokens.append(t)
                seen[rid] = seen.get(rid, 0) + 1
            if rec.done:
                live.pop(rid, None)

        def observe(t: float) -> None:
            for st in eng._slots.values():
                rid = st.req.rid
                if len(st.tokens) > seen.get(rid, 0):
                    deliver(rid, len(st.tokens), t)
            for rid in [r for r in live if r in eng.results]:
                res = eng.results[rid]
                if res.rejected:
                    recs[rid].failed = res.reason or "rejected"
                    live.pop(rid)
                else:
                    deliver(rid, len(res.tokens), t)

        def submit(a, t: float) -> None:
            rec = recs[a.rid]
            rec.submitted = t
            live[a.rid] = rec
            eng.submit(program.request(a.rid, tr.tokens(a.rid, a.prompt_len),
                                       a.out_len))

        while True:
            t = now()
            while pending_hooks and pending_hooks[0][0] <= t:
                pending_hooks.pop(0)[1]()
            if tr.backlog:
                while (nxt < len(sched)
                       and eng.scheduler.pending < tr.backlog):
                    sched[nxt].t = t
                    recs[sched[nxt].rid].arrival = t
                    submit(sched[nxt], t)
                    nxt += 1
            else:
                while nxt < len(sched) and sched[nxt].t <= t:
                    submit(sched[nxt], t)
                    nxt += 1
            observe(now())              # rejections at submit
            with TraceAnnotation(ADMIT):
                while True:
                    pairs = eng.scheduler.admit(
                        tick, fits=lambda r: eng._fits(r, tick), limit=1)
                    if not pairs:
                        break
                    slot, req = pairs[0]
                    a0, cut = now(), eng._plan(req)[0]
                    rec = recs[req.rid]
                    if math.isnan(rec.admitted):
                        rec.admitted, rec.prefill_len = a0, cut
                    ok = eng._admit(slot, req, tick)
                    a1 = now()
                    admissions.append(Admission(a0, a1, req.rid, cut))
                    observe(a1)
                    if not ok:
                        break
            if eng.active.any():
                slots = sorted(eng._slots)
                replaying = [eng._slots[s].req.rid for s in slots
                             if not eng._slots[s].tokens]
                kv = eng.pos[slots].astype(np.int64) + 1
                s0 = now()
                with TraceAnnotation(DECODE):
                    eng.step(tick)
                s1 = now()
                ticks.append(Tick(s0, s1, kv))
                for rid in replaying:
                    recs[rid].replay_ticks += 1
                observe(s1)
            else:
                wake = (sched[nxt].t if not tr.backlog and nxt < len(sched)
                        else t + 1e-3)
                if pending_hooks:
                    wake = min(wake, pending_hooks[0][0])
                with TraceAnnotation(WAIT):
                    time.sleep(max(0.0, min(wake - now(), 0.05)))
            tick += 1
            t = now()
            due_in_window = (not tr.backlog and nxt < len(sched)
                             and sched[nxt].t < w1)
            if t >= w1 and not pending_hooks and not due_in_window:
                open_in_window = any(w0 <= r.arrival < w1 for r in live.values())
                if not open_in_window:
                    break
                if t >= w1 + self.drain_limit:
                    for r in live.values():
                        if w0 <= r.arrival < w1:
                            r.failed = "unfinished"
                    break
        log.end = now()
        return log
