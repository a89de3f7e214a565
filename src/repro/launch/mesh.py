"""Production mesh construction (deliverable e).

A v5e pod is 16x16 = 256 chips; the multi-pod configuration is 2 pods = 512
chips with a leading 'pod' axis (data parallelism over DCN).  Defined as a
FUNCTION so importing this module never touches jax device state.
"""
from __future__ import annotations

import jax


def _mesh(shape, axes, devices=None):
    """Auto axes: GSPMD propagates shardings from the explicit constraints
    (``jax.make_mesh`` defaults to Explicit axes)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_plan_mesh(d: int, t: int, devices=None):
    """Mesh for a MARP plan (d data x t model shards) on ``devices``
    (default: all local devices)."""
    return _mesh((d, t), ("data", "model"), devices)
