"""Per-layer metrics: ``metrics/<name>.py`` holds one reader,
``read(run) -> float | None``, of the finished run (``bench.run.Run``).  A
reader that finds nothing to read returns None and the metric is left out
of the result line; a share of a roofline or a peak is never reported as 0
for want of data."""

from __future__ import annotations

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{name}", os.path.join(HERE, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
