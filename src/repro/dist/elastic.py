"""Elastic mesh planning: pick a (data, model) mesh for whatever device count
survives, so training resumes after node loss instead of waiting for repair.

The recovery contract (tests/test_elastic.py): a checkpoint written under
mesh A restores under a smaller mesh B — parameters are saved unsharded-
logical and resharded with make_shardings on restore, so only the mesh
factorization needs recomputing here.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
from jax.sharding import AxisType


def _best_model_axis(ndev: int, prefer_model: int) -> int:
    """Largest model-parallel degree <= prefer_model that divides ndev.

    Model parallelism is the latency-critical axis (per-layer collectives),
    so we keep it as close to the tuned size as the device count allows and
    absorb the remainder into data parallelism.
    """
    for m in range(min(max(prefer_model, 1), ndev), 0, -1):
        if ndev % m == 0:
            return m
    return 1


def degraded_meshes(ndev: int, losses: Sequence[int], prefer_model: int = 1
                    ) -> List[Tuple[int, Tuple[int, int]]]:
    """Mesh plan per failure scenario: [(remaining, (data, model)), ...].

    losses are device counts lost (0 = healthy).  Scenarios that lose every
    device are omitted.
    """
    out: List[Tuple[int, Tuple[int, int]]] = []
    for loss in losses:
        n = ndev - loss
        if n <= 0:
            continue
        m = _best_model_axis(n, prefer_model)
        out.append((n, (n // m, m)))
    return out


def choose_mesh(ndev: int | None = None, prefer_model: int = 1):
    """(data, model) Mesh over the devices currently visible to jax.  Auto
    axis types: the model code places its arrays with GSPMD sharding
    constraints (``dist.api.constrain``), which Explicit axes refuse."""
    n = ndev if ndev is not None else jax.device_count()
    m = _best_model_axis(n, prefer_model)
    return jax.make_mesh((n // m, m), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
