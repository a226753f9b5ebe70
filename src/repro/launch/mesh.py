"""Production meshes (functions, not module constants — importing this
module never touches jax device state).

Target hardware: TPU v5e pods, 256 chips each (16x16), optionally 2 pods.
  single-pod: (16, 16)      axes ("data", "model")
  multi-pod : (2, 16, 16)   axes ("pod", "data", "model")

Hardware constants for the roofline analysis live here too. Meshes use
``AxisType.Auto`` axes (``jax.make_mesh`` defaults to ``Explicit``), so
sharding stays the compiler's choice inside ``jit``.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = [
    "make_production_mesh", "make_host_mesh",
    "PEAK_FLOPS", "HBM_BW", "ICI_BW",
]

# TPU v5e-class chip (assignment constants)
PEAK_FLOPS = 197e12   # bf16 FLOP/s per chip
HBM_BW = 819e9        # bytes/s per chip
ICI_BW = 50e9         # bytes/s per ICI link


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — for tests/examples."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"need {data * model} devices, have {n}")
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
