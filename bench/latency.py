"""End-to-end metrics from the driver's log, on the host clock.

- ``ttft_p90_ms``: 90th percentile, over the requests that arrived inside
  the window, of scheduled arrival to first token.  A request that failed
  or never finished counts as infinitely late.
- ``itl_p50_ms`` / ``itl_p95_ms`` / ``itl_p99_ms``: median, 95th and 99th
  percentile of the gaps between successive tokens of one request, over
  every gap of every request that arrived inside the window (followed to
  its end after the close).
- ``output_tokens_per_s``: tokens delivered inside the window, over its
  length.

Percentiles interpolate linearly between order statistics (numpy's
default)."""

from __future__ import annotations

import math
from typing import Dict, List

import numpy as np


def percentile(values, q: float) -> float:
    v = np.asarray(values, np.float64)
    if v.size == 0:
        return math.nan
    if np.isinf(v).any():
        # a missing answer is later than any: rank without interpolating
        # across infinity
        v = np.sort(v)
        k = q / 100.0 * (v.size - 1)
        lo, hi = int(math.floor(k)), int(math.ceil(k))
        if math.isinf(v[hi]):
            return math.inf
        return float(v[lo] + (v[hi] - v[lo]) * (k - lo))
    return float(np.percentile(v, q))


def ttfts(recs) -> List[float]:
    return [(r.tokens[0] - r.arrival) if r.tokens and not r.failed
            else math.inf for r in recs]


def gaps(recs) -> np.ndarray:
    parts = [np.diff(r.tokens) for r in recs if len(r.tokens) > 1]
    return np.concatenate(parts) if parts else np.zeros(0)


def end_to_end(log) -> Dict[str, float]:
    recs = log.in_window()
    w0, w1 = log.window
    g = gaps(recs)
    delivered = sum(1 for r in log.recs.values() for t in r.tokens
                    if w0 <= t < w1)
    return {"ttft_p90_ms": 1e3 * percentile(ttfts(recs), 90),
            "itl_p50_ms": 1e3 * percentile(g, 50),
            "itl_p95_ms": 1e3 * percentile(g, 95),
            "itl_p99_ms": 1e3 * percentile(g, 99),
            "output_tokens_per_s": delivered / (w1 - w0)}
