"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state; the dry-run sets XLA_FLAGS before first jax use.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    # Auto axes: the model code places values with with_sharding_constraint,
    # which jax.make_mesh's default Explicit axes reject
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Elastic-scaling helper: build a mesh for whatever devices exist."""
    return _auto_mesh(shape, axes)


def make_host_mesh(model_axis: Optional[int] = None):
    """Smoke-scale mesh over the real local devices (CPU: 1 device)."""
    n = len(jax.devices())
    m = model_axis or 1
    return _auto_mesh((n // m, m), ("data", "model"))


# TPU v5e hardware constants (roofline targets; this container is CPU-only).
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link (~4 links usable/chip)
CHIPS_PER_POD = 256
