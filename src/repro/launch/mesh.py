"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import; smoke tests and benchmarks see the real single device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_mesh(shape, axes) -> Mesh:
    """Arbitrary mesh (tests, small runs)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape))


def host_device_mesh() -> Mesh:
    """All local devices on one ('data','model') mesh with model=1 (used by
    the example drivers on CPU)."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))
