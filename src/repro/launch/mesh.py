"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — dryrun.py must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
device initialization.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the model code places arrays with with_sharding_constraint,
    # which JAX refuses on the Explicit axes that make_mesh defaults to
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single-pod: (data=16, model=16) = 256 chips.  Multi-pod: an outer
    "pod" data-parallel axis on top — (pod=2, data=16, model=16) = 512."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2, *, multi_pod: bool = False):
    """Small mesh for in-process tests (requires enough host devices)."""
    if multi_pod:
        return _mesh((2, n_data, n_model), ("pod", "data", "model"))
    return _mesh((n_data, n_model), ("data", "model"))
