"""Prompt and output lengths: lognormal, clipped, as the mix file states
(``prompt`` and ``output``: ``median``, ``sigma``, ``min``, ``max``).

With ``palette: P`` a spec draws P lengths once from its distribution (fixed
by the mix's ``base_seed``) and every request takes one of them uniformly,
so a run holds at most P distinct lengths.  The serving engine compiles
small executables for each distinct prompt length it admits (about 0.2 s
each on a v5e chip), so prompt lengths come from a palette."""

from __future__ import annotations

import numpy as np


def lognormal(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def column(spec: dict, n: int, base: np.random.Generator,
           palette_seed) -> np.ndarray:
    if "palette" not in spec:
        return lognormal(spec, n, base)
    palette = lognormal(spec, int(spec["palette"]),
                        np.random.default_rng(palette_seed))
    return palette[base.integers(0, len(palette), size=n)]


def draw(mix: dict, n: int, base: np.random.Generator,
         run: np.random.Generator):
    """``n`` (prompt, output) lengths: a multiset fixed by ``base``, each
    column put in an order of ``run``'s."""
    seed = int(mix["base_seed"])
    prompts = column(mix["prompt"], n, base, [seed, 1])
    outs = column(mix["output"], n, base, [seed, 2])
    return run.permutation(prompts), run.permutation(outs)
