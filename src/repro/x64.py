"""The one switch into 64-bit mode for the f64 paths.

The jax decide backend, the lowered tree descent and the fleet scan run
their arrays in f64 so that, on the CPU, they stay bit-for-bit with the
numpy references they are tested against.
"""
from __future__ import annotations

import jax


def x64():
    """Context manager: jax creates 64-bit arrays inside it."""
    return jax.enable_x64(True)
