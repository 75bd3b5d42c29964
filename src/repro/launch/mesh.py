"""Mesh builder for the sharded fleet (a FUNCTION, not module state —
importing this never touches jax device initialization).

Every axis is ``Auto``: shardings are propagated by the compiler from the
``PartitionSpec``s ``jax.shard_map`` is given (``jax.make_mesh`` would
otherwise make ``Explicit`` axes)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))
