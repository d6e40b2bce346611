"""Production mesh construction.

Pure function — importing this module never touches jax device state, so
smoke tests see 1 device while the dry-run (which sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import)
sees the full placeholder fleet.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = [
    "factor_2d",
    "make_group_mesh",
    "make_production_mesh",
    "make_mesh",
    "set_mesh",
]


def factor_2d(ndev: int):
    """Squarest (a, b) factoring of a device count, a <= b.

    The one definition of how ``--qshard 2d`` (and the benchmark that mirrors
    it) splits a flat device fleet into a (structure, batch) grid.
    """
    a = int(ndev**0.5)
    while ndev % a:
        a -= 1
    return a, ndev // a


def _mk(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh for tests/examples (e.g. (2, 4) on 8 fake devices)."""
    return _mk(tuple(shape), tuple(axes))


def make_group_mesh(devices, axes=("shard",)):
    """1-D mesh over an *explicit* device subset.

    ``jax.make_mesh`` always spans every visible device; a replica fleet
    (``serve.fleet``) instead carves the fleet into disjoint per-replica
    groups, each serving a mesh engine on its own slice of the devices.
    """
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.asarray(devices, dtype=object), tuple(axes))


def set_mesh(mesh):
    """Context manager activating ``mesh`` (``jax.set_mesh``)."""
    return jax.set_mesh(mesh)
