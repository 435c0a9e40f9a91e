"""Production mesh builders (DESIGN.md §4).

Functions, not module-level constants — importing this module never touches
jax device state; the dry-run sets XLA_FLAGS before any jax import.
"""
from __future__ import annotations

import jax


def _mk(shape, axes):
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(shape, axes):
    return _mk(tuple(shape), tuple(axes))


def make_host_mesh(model_parallel: int = 1):
    """Whatever this host offers (tests/CPU benches): (n/mp, mp)."""
    n = len(jax.devices())
    mp = model_parallel
    while n % mp:
        mp -= 1
    return _mk((n // mp, mp), ("data", "model"))
