"""Production mesh construction (multi-pod dry-run target).

A FUNCTION, not a module-level constant — importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips (one v5e pod), or 2×16×16 = 512 (two pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Whatever this process actually has (smoke tests, live executor)."""
    n = len(jax.devices())
    return jax.make_mesh((1, n), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
