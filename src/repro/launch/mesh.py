"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (device count is locked on first jax init, and
only dryrun.py is allowed to force 512 host devices).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 chips per pod ("data","model"); 2 pods adds a leading "pod"
    axis. v5e pod slice = 256 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_local_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (CPU tests)."""
    return jax.make_mesh(
        (data, model), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )
