"""Model step, decode: device time of the decode executable per tick, from
the trace.  In each ``bench.decode`` span the decode executable is the
longest module that ran; the metric is its mean duration."""

from bench import trace as tr


def read(run):
    if run.trace is None:
        return None
    per = []
    for mods in run.trace.modules:
        spans = tr.within(mods, run.trace.spans, "bench.decode")
        per += [max(e.dur for e in evs) for evs in spans.values()]
    return 1e3 * sum(per) / len(per) if per else None
