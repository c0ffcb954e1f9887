"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state. The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import (see dryrun.py) so these meshes can be built on a CPU-only container.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """A mesh whose axes all let XLA's partitioner place shardings.

    ``jax.make_mesh`` defaults to Explicit axes, under which every op must
    carry a sharding in its type; the serving and dry-run paths shard their
    inputs and leave propagation to the partitioner, so they need Auto.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod (v5e pod); 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host actually has — smoke tests / local runs."""
    n = len(jax.devices())
    return _auto_mesh((n, 1), ("data", "model"))
