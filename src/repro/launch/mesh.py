"""Production mesh definitions.

Functions, not module-level constants: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before any jax import; smoke
tests see the single real CPU device).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


# TPU v5e hardware constants used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9            # bytes/s per chip
ICI_BW = 50e9             # bytes/s per link


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(*, data: int = 1, model: int = 1):
    """A ``(data, model)`` mesh over the local devices (tests / examples).
    Raises when there are fewer devices than the mesh asks for."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"a {data}x{model} mesh needs {data * model} "
                         f"devices; {n} available")
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto),
                         devices=jax.devices()[:data * model])


def chips(mesh) -> int:
    return mesh.devices.size
