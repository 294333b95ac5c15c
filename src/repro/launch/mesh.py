"""Production mesh construction (multi-pod dry-run spec).

Defined as functions (not module-level constants) so importing this module
never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.configs.base import MeshConfig


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; 2 pods = 512 chips multi-pod. Axis order puts
    ``pod`` outermost so cross-pod collectives map to the DCI dimension."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def mesh_config(*, multi_pod: bool = False) -> MeshConfig:
    if multi_pod:
        return MeshConfig((2, 16, 16), ("pod", "data", "model"))
    return MeshConfig((16, 16), ("data", "model"))


def make_mesh_from_config(cfg: MeshConfig):
    return jax.make_mesh(cfg.shape, cfg.axes,
                         axis_types=(AxisType.Auto,) * len(cfg.axes))


# TPU v5e hardware constants (roofline targets; this container is CPU-only)
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link
