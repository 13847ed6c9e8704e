"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state.  Single-pod: 16×16 = 256 chips (v5e pod); multi-pod: 2×16×16 = 512
chips with a leading "pod" axis (pure DP over DCN).
"""
from __future__ import annotations

import jax

__all__ = ["make_mesh", "make_production_mesh", "make_local_mesh"]


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the models' sharding hints and
    GSPMD placement assume them (``jax.make_mesh`` defaults to Explicit)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — tests/examples."""
    return make_mesh((data, model), ("data", "model"))
