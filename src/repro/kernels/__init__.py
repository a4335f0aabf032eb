"""Pallas TPU kernels of the int4 cache, and what they share."""
from __future__ import annotations

import jax
import numpy as np


def half_major(d: int) -> np.ndarray:
    """Column order of the kernels' int4 codes: even coordinates, then odd.

    Byte ``i`` packs coordinates ``2i`` (low nibble) and ``2i+1`` (high
    nibble), so unpacking into ``[low | high]`` halves needs no lane
    interleave, which Mosaic does not lower.  The kernels permute their
    fp32 operands into this order and undo it on their outputs.
    """
    return np.concatenate([np.arange(0, d, 2), np.arange(1, d, 2)])


def interpret_default() -> bool:
    """Interpret only on the CPU backend; every other backend compiles."""
    return jax.default_backend() == "cpu"
