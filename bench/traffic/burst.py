"""Open-loop bursts: burst starts are Poisson, a burst's size is geometric
with mean ``burst.mean``, and its arrivals are spread uniformly over
``burst.spread_s`` after its start.

A phase of length T holds round(rate * T) arrivals, so the mean rate is the
cell's ``rate``: sizes are drawn until they cover that count (the last one
cut to fit), and the bursts' start gaps are exponential draws scaled to sum
to T.  Sizes and gaps are fixed multisets per phase, put in the run's order;
the offsets inside a burst are the run's own draws."""

from __future__ import annotations

import numpy as np

from bench.traffic.poisson import gaps

NEEDS_RATE = True


def arrivals(mix, phases, rate, base, run):
    mean, spread = mix["burst"]["mean"], mix["burst"]["spread_s"]
    out = []
    for _, start, length in phases:
        n = int(round(rate * length))
        sizes = []
        while sum(sizes) < n:
            sizes.append(min(int(base.geometric(1.0 / mean)), n - sum(sizes)))
        nb = len(sizes)
        sizes = run.permutation(np.asarray(sizes, np.int64))
        g = gaps(nb, length, base, run)
        starts = start + np.concatenate([[0.0], np.cumsum(g[:-1])])[:nb]
        t = [s0 + run.uniform(0.0, spread, size=k)
             for s0, k in zip(starts, sizes)]
        out.append(np.sort(np.concatenate(t)) if t else np.zeros(0))
    return out, 0
