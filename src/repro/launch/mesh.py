"""Production mesh builders (a FUNCTION, not module state — importing this
never touches jax device initialization).

Every axis is ``Auto``: shardings are propagated by the compiler from the
``PartitionSpec``s the callers give, as ``jax.shard_map`` and the sharding
rules expect (``jax.make_mesh`` would otherwise make ``Explicit`` axes)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape, axes):
    return _auto_mesh(shape, axes)


def mesh_chips(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
