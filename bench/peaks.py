"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A kind that is not in ``peaks.json`` is an
error: a roofline share against a guessed peak is no measurement."""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks_for(device_kind: str, path: str = PEAKS_FILE) -> dict:
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
