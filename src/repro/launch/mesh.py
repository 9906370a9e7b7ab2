"""Production meshes.

Functions, not module-level constants — importing this module never touches
jax device state (device count locks on first use)."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(n: int = 1, axes=("data",)):
    """Small host mesh for tests/examples on forced CPU devices."""
    devs = jax.devices()[:n]
    import numpy as np
    shape = (n,) if len(axes) == 1 else None
    return jax.sharding.Mesh(np.array(devs).reshape(shape), axes)


# TPU v5e hardware constants (roofline targets; this container is CPU-only)
PEAK_FLOPS_BF16 = 197e12       # per chip
HBM_BW = 819e9                 # bytes/s per chip
ICI_BW = 50e9                  # bytes/s per link
