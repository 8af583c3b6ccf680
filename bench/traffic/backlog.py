"""Closed backlog: ``queued`` requests wait in the queue at all times; a
new one is submitted whenever fewer wait.  ``count`` bounds the requests a
run can draw."""

from __future__ import annotations

import numpy as np

NEEDS_RATE = False


def arrivals(mix, phases, rate, base, run):
    return [np.zeros(int(mix["count"]))], int(mix["queued"])
