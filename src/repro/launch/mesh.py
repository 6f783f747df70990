"""Production meshes.  A FUNCTION, not a module-level constant: importing
this module never touches jax device state.

Every mesh is built with ``AxisType.Auto`` axes: ``jax.make_mesh`` defaults
to Explicit axes, under which eager (un-jitted) ops on arrays sharded over
the mesh fail unless the caller has entered ``jax.set_mesh``.  The SNN
engines shard their state with ``NamedSharding``/``shard_map`` and touch it
eagerly between chunks (monitors, checkpoint sync, fault injection), so
Auto is the sharding mode they are written for."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices=None):
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod ("data","model"); multi-pod adds a leading
    "pod" axis (2 pods = 512 chips, pure-DP across pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_snn_mesh(k: int, devices=None):
    """1D partition mesh for the distributed SNN simulator: one partition
    per device of ``devices`` (default: the first ``k`` of
    ``jax.devices()``)."""
    if devices is None:
        devices = jax.devices()[:k]
    return _auto_mesh((k,), ("parts",), devices=devices)
