"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (required so smoke tests see 1 CPU device
while the dry-run sees 512 fake ones).

Every mesh here has ``Auto`` axes: the model code places activations
with ``with_sharding_constraint``, which only accepts Auto axes
(``jax.make_mesh`` defaults to Explicit ones).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_local_mesh"]


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = one 256-chip v5e pod; 2×16×16 = two pods (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (real or fake) devices exist —
    used by multi-device CPU tests."""
    return make_mesh((data, model), ("data", "model"))
