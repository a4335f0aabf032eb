"""Production mesh construction (TPU v5e target).

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (smoke tests must keep seeing 1 CPU device; the
dry-run sets XLA_FLAGS for 512 host devices before any jax import).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "data_axes", "HW"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def data_axes(mesh) -> tuple:
    """Batch-sharding axes: ('pod', 'data') when the pod axis exists."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


class HW:
    """TPU v5e roofline constants (per chip)."""

    PEAK_BF16_FLOPS = 197e12  # FLOP/s
    HBM_BW = 819e9  # B/s
    ICI_BW = 50e9  # B/s per link
    HBM_BYTES = 16 * 1024 ** 3
