"""Scheduler and admission: 90th percentile, over requests that arrived in
the window, of their prompt replay, from the program's own spans: the
``serve.replay`` span, from the end of the admission to the decode tick
that emits the request's first token.  A request whose first token came
from prefill replays for 0 ms; one that never emitted a token (never
admitted, or still replaying) counts as infinitely late."""

import math

from bench import program_spans
from bench.latency import percentile


def read(run):
    spans = program_spans.recorded(run)
    if not spans:
        return None
    replay = {}
    for s in spans:
        if s.name == "serve.replay":
            replay.setdefault(s.rid, s.end - s.start)
    vals = [replay.get(r.rid, 0.0) if r.tokens else math.inf
            for r in run.log.in_window()]
    v = percentile(vals, 90)
    return None if math.isnan(v) or math.isinf(v) else 1e3 * v
