"""Production meshes.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Single pod: 256 chips as (16 data x 16 model).  Multi
pod: 2 pods x 256 chips, the "pod" axis being an extra data-parallel (or
pipeline) dimension that crosses the DCN boundary.
"""
from __future__ import annotations

import jax


def _auto(n_axes: int) -> tuple:
    """Auto (GSPMD-propagated) axes; ``jax.make_mesh`` defaults to
    Explicit, which the sharding rules here do not use."""
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_mesh_for_devices(n_data: int, n_model: int, pods: int = 1):
    """Smaller meshes for tests (same axis conventions)."""
    if pods > 1:
        return jax.make_mesh(
            (pods, n_data, n_model), ("pod", "data", "model"),
            axis_types=_auto(3),
        )
    return jax.make_mesh(
        (n_data, n_model), ("data", "model"), axis_types=_auto(2)
    )


# v5e-class hardware constants used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
