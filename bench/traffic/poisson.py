"""Open-loop Poisson arrivals at the cell's rate.

Each phase of length T holds round(rate * T) arrivals whose gaps are a fixed
multiset of exponential draws scaled to sum to T (a Poisson process
conditioned on its count), put in the run's order."""

from __future__ import annotations

import numpy as np

NEEDS_RATE = True


def gaps(n: int, length: float, base: np.random.Generator,
         run: np.random.Generator) -> np.ndarray:
    g = base.exponential(1.0, size=n)
    return run.permutation(g * (length / g.sum()))


def arrivals(mix, phases, rate, base, run):
    out = []
    for _, start, length in phases:
        n = int(round(rate * length))
        g = gaps(n, length, base, run)
        out.append(start + np.concatenate([[0.0], np.cumsum(g[:-1])])[:n])
    return out, 0
