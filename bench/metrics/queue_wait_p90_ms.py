"""Scheduler and admission: 90th percentile, over requests that arrived in
the window, of scheduled arrival to the start of their admission (host
clock).  A request never admitted counts as infinitely late."""

import math

from bench.latency import percentile


def read(run):
    waits = [(r.admitted - r.arrival) if not math.isnan(r.admitted)
             else math.inf for r in run.log.in_window()]
    v = percentile(waits, 90)
    return None if math.isnan(v) or math.isinf(v) else 1e3 * v
