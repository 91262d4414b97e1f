"""Mesh construction.  ``make_production_mesh`` is a FUNCTION (never a
module-level constant) so importing this module touches no jax device state.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 single pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh_from_plan(plan: Dict[str, int]) -> Mesh:
    """Mesh from an elastic re-plan (runtime/fault_tolerance.plan_mesh)."""
    axes = tuple(a for a in ("pod", "data", "model") if a in plan)
    shape = tuple(plan[a] for a in axes)
    return _make_mesh(shape, axes)


def single_device_mesh() -> Mesh:
    return _make_mesh((1, 1), ("data", "model"))
