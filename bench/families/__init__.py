"""Model families: ``families/<family>.py`` holds all the benchmark knows of
one family of decoders, and the configuration file names its family
(``"family"``).  The harness (``program``, ``reference``, the metric
readers) asks the family and never names one.

A family module gives:

- ``arch_fields(model, base, check)``: the program's ``ArchConfig`` fields
  for the configuration file, checked against the program's own entry
  ``base``; it refuses an entry the family cannot stand for;
- ``params(key, model, cfg, pack)``: the program's whole compressed
  parameter tree, traceable, with ``pack`` the program's packing of one
  dense ``[out, in]`` linear;
- ``layer(key, model, index)``: the dense linears of one layer, from
  ``bench.weights.tensor`` under role ids of the family's own (``ROLES``);
- ``block(model, w, h)`` and ``head(model, emb, h)``: the plain
  reference's arithmetic of one layer and of the LM head, float32;
- the required work of one decode tick, for the kernel readers that the
  family's kernels run (``nm_spmv_linears``, ``paged_attn_work``,
  ``paged_attn_kernel``, ``decode_flops``).  A reader whose function the
  family lacks reports nothing.
"""

from __future__ import annotations

import functools
import importlib.util
import os
from typing import List

DIR = os.path.dirname(os.path.abspath(__file__))


def known() -> List[str]:
    return sorted(f[:-3] for f in os.listdir(DIR)
                  if f.endswith(".py") and not f.startswith("_"))


@functools.lru_cache(maxsize=None)
def _load(path: str):
    name = os.path.basename(path)[:-3]
    spec = importlib.util.spec_from_file_location(
        f"bench.families.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(model: dict):
    """The family module that ``model["family"]`` names, from ``DIR``
    (``bench/families``).  There is no default family."""
    name, names = model.get("family"), known()
    if name not in names:
        raise ValueError(f"{model.get('name', 'configuration')}: family "
                         f"{name!r} is not one of {names}")
    return _load(os.path.join(DIR, f"{name}.py"))
