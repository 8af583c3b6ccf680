"""Production mesh construction.

Single pod: 16x16 = 256 chips (data, model).
Multi-pod:  2x16x16 = 512 chips (pod, data, model) — the pod axis carries
cross-pod data parallelism (gradient all-reduce, optionally compressed).

A function, not a module constant: importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax initialization).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axis types: the model code relies on GSPMD sharding constraints
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
