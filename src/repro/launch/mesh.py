"""Production mesh: 16×16 single pod (256 chips), 2×16×16 multi-pod (512).

``make_production_mesh`` is a function, not a module constant — importing
this module never touches jax device state (the dry-run must set
``xla_force_host_platform_device_count`` before the first device query).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "POD_SHAPE", "MULTI_POD_SHAPE"]

POD_SHAPE = (16, 16)
MULTI_POD_SHAPE = (2, 16, 16)


def make_production_mesh(*, multi_pod: bool = False):
    shape = MULTI_POD_SHAPE if multi_pod else POD_SHAPE
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the activation sharder constrains with
    # with_sharding_constraint, which refuses Explicit mesh axes.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def data_axes(multi_pod: bool) -> tuple[str, ...]:
    return ("pod", "data") if multi_pod else ("data",)
