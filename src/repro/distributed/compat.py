"""Mesh construction for the distributed layer."""
from __future__ import annotations

import jax


def make_auto_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with every axis Auto: XLA propagates the shardings,
    which is the semantics every mesh in this repo relies on."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
