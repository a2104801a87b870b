"""Production mesh construction.

Functions, not module-level constants — importing this module never touches
jax device state (device count locks on first jax init, and the dry-run must
set XLA_FLAGS before that happens).

Mesh shapes follow DESIGN.md: one TPU v5e pod = 16x16 chips = (data=16,
model=16); two pods join over DCN on a leading "pod" axis = (2, 16, 16).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _axis_kw(n_axes: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_kw(len(axes)))


def make_mesh(data: int = 1, model: int = 1, pod: int = 1) -> Mesh:
    """Small/explicit mesh (tests, examples, single-host runs)."""
    if pod > 1:
        return jax.make_mesh((pod, data, model), ("pod", "data", "model"),
                             **_axis_kw(3))
    return jax.make_mesh((data, model), ("data", "model"), **_axis_kw(2))


def single_device_mesh() -> Mesh:
    return make_mesh(1, 1)
