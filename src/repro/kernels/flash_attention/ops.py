"""Public GQA flash-attention wrapper over the Pallas kernel.

Accepts the model zoo layout q [B,S,H,Dh], k/v [B,Skv,KV,Dh]; expands kv
heads, folds (B, H) into the kernel's grid dim, unfolds the result.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import flash_attention_bhsd


@partial(jax.jit, static_argnames=("causal", "q_offset", "bq", "bk",
                                   "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, q_offset: int = 0,
                    bq: int = 128, bk: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    b, sq, h, dh = q.shape
    skv, kv = k.shape[1], k.shape[2]
    g = h // kv
    kx = jnp.repeat(k, g, axis=2) if g > 1 else k
    vx = jnp.repeat(v, g, axis=2) if g > 1 else v
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, dh)
    kf = kx.transpose(0, 2, 1, 3).reshape(b * h, skv, dh)
    vf = vx.transpose(0, 2, 1, 3).reshape(b * h, skv, dh)
    of = flash_attention_bhsd(qf, kf, vf, causal=causal, q_offset=q_offset,
                              bq=bq, bk=bk, interpret=interpret)
    return of.reshape(b, h, sq, dh).transpose(0, 2, 1, 3)
