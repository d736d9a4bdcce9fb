"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Shapes per the deployment spec:
  single pod:  (data=16, model=16)           = 256 chips (TPU v5e pod)
  multi-pod:   (pod=2, data=16, model=16)    = 512 chips
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_debug_mesh(shape=(4, 2), axes=("data", "model")):
    """Small mesh for CPU-hosted tests (XLA_FLAGS device_count >= prod(shape))."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
