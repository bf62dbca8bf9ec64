"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run sets the 512-device XLA flag before
first jax init; everything else sees the real topology).

Production target: TPU v5e pods, 16x16 = 256 chips per pod; multi-pod adds
a leading "pod" axis (2 pods = 512 chips for the dry-run; the axis scales
to N pods unchanged — DCN-connected, so only batch/database rows shard
over it).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """jax.make_mesh with Auto axes — the ONE mesh constructor;
    benchmarks, examples, and tests that build ad-hoc meshes route
    through here."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for multi-device unit tests (8 host devices)."""
    return make_mesh((n_data, n_model), ("data", "model"))
