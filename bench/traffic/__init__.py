"""Traffic generators.  A mix is ``traffic/<mix>.json``; its ``kind`` names
the generator module ``traffic/<kind>.py``, whose ``arrivals(mix, phases,
rate, rng_base, rng_run)`` returns the arrival times of each phase, sorted,
and the backlog depth.  Lengths are drawn by
``lengths.py`` for every kind alike."""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
from typing import Callable, List, Optional

import numpy as np

from bench.traffic import lengths

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Arrival:
    rid: int
    t: float              # scheduled arrival, seconds from the loop's start
    prompt_len: int
    out_len: int


@dataclasses.dataclass
class Traffic:
    """A run's schedule.  Open loop: ``arrivals`` sorted by ``t``.  Closed
    backlog (``backlog`` > 0): ``arrivals`` is the order of submission, each
    submitted when fewer than ``backlog`` requests are queued, and ``t`` is
    unused."""
    arrivals: List[Arrival]
    backlog: int
    tokens: Callable[[int, int], np.ndarray]    # (rid, length) -> ids


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, f"{name}.json")) as f:
        return json.load(f)


def build(mix: dict, *, seed: int, vocab: int, warmup_s: float,
          window_s: float, rate: Optional[float]) -> Traffic:
    """The whole schedule of one run: warm-up, window and after it.

    The sizes and gaps of each phase (warm-up, window, after) are a fixed
    multiset drawn from the mix's ``base_seed``; ``seed`` only orders them
    inside the phase and draws the token ids.  So every seed offers the same
    work to each phase, in another order."""
    kind = importlib.import_module(f"bench.traffic.{mix['kind']}")
    if kind.NEEDS_RATE and not rate:
        raise ValueError(f"mix kind {mix['kind']!r} needs the cell's rate")
    base = np.random.default_rng(int(mix["base_seed"]))
    run = np.random.default_rng(int(seed))
    phases = [("warmup", 0.0, warmup_s), ("window", warmup_s, window_s),
              ("after", warmup_s + window_s, float(mix["after_s"]))]
    per_phase, backlog = kind.arrivals(mix, phases, rate, base, run)
    drawn = [lengths.draw(mix, len(t), base, run) for t in per_phase]
    times = np.concatenate(per_phase)
    prompts = np.concatenate([p for p, _ in drawn])
    outs = np.concatenate([o for _, o in drawn])
    n = len(times)
    arrivals = [Arrival(rid=i, t=float(times[i]), prompt_len=int(prompts[i]),
                        out_len=int(outs[i])) for i in range(n)]

    def tokens(rid: int, length: int) -> np.ndarray:
        r = np.random.default_rng([int(seed) % (2 ** 63), rid])
        return r.integers(0, vocab, size=length, dtype=np.int32)

    return Traffic(arrivals=arrivals, backlog=backlog, tokens=tokens)
