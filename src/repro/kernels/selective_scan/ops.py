"""Jit'd wrapper for the selective-scan kernel (``interpret=True`` runs it
through the Pallas interpreter, as the CPU tests do)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import selective_scan as _kernel


@partial(jax.jit, static_argnames=("chunk", "bd", "interpret"))
def selective_scan(a: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray, *,
                   chunk: int = 128, bd: int = 256,
                   interpret: bool = False) -> jnp.ndarray:
    return _kernel(a, b, c, chunk=chunk, bd=bd, interpret=interpret)
