"""Production mesh construction.

Kept as functions (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
``XLA_FLAGS`` before the first jax initialization.
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2, n_pod: int = 0):
    """Small host-device mesh for distributed CPU tests."""
    if n_pod:
        return _auto_mesh((n_pod, n_data, n_model), ("pod", "data", "model"))
    return _auto_mesh((n_data, n_model), ("data", "model"))


# TPU v5e hardware constants for the roofline model (per chip)
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW_PER_LINK = 50e9            # B/s per link
