"""What one cell is: its entry in ``BENCHMARK.json`` and the files that
entry names, found by name under ``bench/``.

- ``configs/<config>.json``: the model as run, with its engine geometry;
- ``families/<family>.py``: the model family the configuration names
  (``families/__init__.py``);
- ``traffic/<mix>.json``: the traffic mix (``traffic/__init__.py``);
- ``cells/<workload>.json``: what belongs to the pair alone: the offered
  rate of an open-loop cell and the limits of its output check;
- ``metrics/<metric>.py``: one reader per per-layer metric.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix_name: str
    mix: dict
    cell: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def reports(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def load(workload: str, bench_file: str = os.path.join(
        ROOT, "BENCHMARK.json")) -> Cell:
    b = _load(bench_file)
    cells = {w["name"]: w for w in b["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in b["configs"]}
    config = _load(os.path.join(ROOT, configs[w["config"]]["file"]))
    from bench import traffic
    e2e = [m for m in b["end_to_end"] if reports(m, workload)]
    moved = {m["name"] for m in e2e}
    # a per-layer metric without a ``workloads`` list is reported wherever
    # the end-to-end metric it moves is
    per_layer = [m for m in b["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    cell_file = os.path.join(HERE, "cells", f"{workload}.json")
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                mix_name=w["traffic"], mix=traffic.load_mix(w["traffic"]),
                cell=_load(cell_file) if os.path.exists(cell_file) else {},
                end_to_end=e2e, per_layer=per_layer)
