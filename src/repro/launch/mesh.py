"""Production meshes.

A function, not a module-level constant: importing this module never
touches jax device state (contract requirement — device count is locked at
first jax init, and only launch/dryrun.py sets the 512-device flag).

Every mesh here uses ``AxisType.Auto`` axes: the model code shards by
logical-axis rules and lets the partitioner propagate, so none of its
axes are explicit.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (fake or real) devices exist —
    used by tests and CPU examples."""
    n = len(jax.devices())
    if data * model > n:
        data, model = 1, 1
    return _auto_mesh((data, model), ("data", "model"))


def chips(mesh) -> int:
    return int(mesh.devices.size)
