"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
initialisation, while smoke tests run on the single real CPU device.

Every axis is ``AxisType.Auto``: the programs place arrays with
``with_sharding_constraint`` and logical-axis rules and let the
partitioner propagate the rest, which the Explicit axes that
``jax.make_mesh`` defaults to do not allow.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2, *,
                    multi_pod: bool = False, pods: int = 2):
    """Small mesh for CPU-host distribution tests (needs
    ``--xla_force_host_platform_device_count`` >= the product)."""
    if multi_pod:
        return _auto_mesh((pods, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))
