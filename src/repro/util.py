"""Small shared utilities.

``scan`` wraps ``jax.lax.scan`` with a process-wide UNROLL switch: XLA's
cost analysis counts a while-loop body ONCE, so roofline-counting compiles
run under ``unrolled_counting()`` which makes every repro scan fully
unroll (depth-1/2 model variants keep the unrolled op count small).

``enable_compile_cache`` turns on JAX's persistent compilation cache; the
entry points (``chip_smoke.py``, ``benchmarks/``, ``examples/``) call it
first thing, importing the library never does.
"""
from __future__ import annotations

import contextlib
import os
import threading
from pathlib import Path
from typing import Optional

import jax

_state = threading.local()

# fixed, so that one checkout's runs find each other's entries (the path
# is part of the cache key); git-ignored
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Keep compiled programs across processes.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
    changes nothing (returns None); otherwise the cache goes to
    ``COMPILE_CACHE_DIR`` in the checkout (returned).  Nothing depends on
    an entry being there: a miss compiles."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    from jax.experimental.compilation_cache import compilation_cache
    path = str(COMPILE_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    # JAX decides once per process whether the cache is on, at its first
    # compile; make it decide again in case something compiled already
    compilation_cache.reset_cache()
    return path


def _unroll() -> bool:
    return getattr(_state, "unroll", False)


@contextlib.contextmanager
def unrolled_counting():
    prev = getattr(_state, "unroll", False)
    _state.unroll = True
    try:
        yield
    finally:
        _state.unroll = prev


def scan(f, init, xs, length=None, unroll=None):
    """jax.lax.scan that fully unrolls under ``unrolled_counting()``."""
    if unroll is None:
        unroll = True if _unroll() else 1
    return jax.lax.scan(f, init, xs, length=length, unroll=unroll)
