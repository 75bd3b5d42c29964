"""Jit'd public wrapper: APSP via Pallas min-plus squaring.

Compiles for the TPU by default; pass ``interpret=True`` to run the
kernel through the Pallas interpreter (how the CPU tests run it).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import minplus_matmul


@partial(jax.jit, static_argnames=("steps", "interpret", "block"))
def apsp(adj: jnp.ndarray, *, steps: int | None = None,
         interpret: bool = False, block: int = 128) -> jnp.ndarray:
    """Tropical-semiring all-pairs shortest paths.

    adj: [n, n] edge weights (inf = no edge, 0 diagonal).
    """
    n = adj.shape[0]
    steps = steps if steps is not None else max(1, int(np.ceil(np.log2(n))))
    d = adj.astype(jnp.float32)
    for _ in range(steps):
        d = minplus_matmul(d, d, bm=block, bn=block, bk=block,
                           interpret=interpret)
    return d
