"""Engine host loop: device idle time per decode tick, from the trace.  The
program's ``serve.step`` spans are mapped onto the trace's clock
(``program_spans.offset``); the metric is the device's idle time inside
those that lie in the traced window, per span (averaged over the chips
used)."""

import bisect

from bench import program_spans
from bench import trace as tr


def overlap_s(gaps, spans) -> float:
    """Seconds of the sorted disjoint ``gaps`` that fall inside ``spans``."""
    starts = [s for s, _ in gaps]
    total = 0.0
    for sp in spans:
        i = max(bisect.bisect_right(starts, sp.start) - 1, 0)
        while i < len(gaps) and gaps[i][0] < sp.end:
            total += max(0.0, min(gaps[i][1], sp.end)
                         - max(gaps[i][0], sp.start))
            i += 1
    return total


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    steps = program_spans.mapped(run, "serve.step")
    if not steps:
        return None
    idle = sum(overlap_s(tr.idle_gaps(ops, run.trace.window), steps)
               for ops in run.trace.ops) / len(run.trace.ops)
    return 1e3 * idle / len(steps)
