"""Jit'd public wrapper: each packet's Eq. 3 bottleneck share through the
Pallas kernel, from the engine's layouts (``share_l`` [L], route links
[P, H] with -1 padding).

Compiles for the TPU by default; pass ``interpret=True`` to run the
kernel through the Pallas interpreter (how the CPU tests run it).  It
batches under ``vmap`` (fleet lanes, policy batches) like any primitive.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import LANES, bottleneck_lanes

BLOCK = 512     # packets per grid step (a multiple of 128)
ROWS = 16       # table rows padded to a bf16 sublane tile (the MXU's K)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


@partial(jax.jit, static_argnames=("interpret",))
def eq3_bottleneck(share_l: jnp.ndarray, route_links: jnp.ndarray, *,
                   interpret: bool = False) -> jnp.ndarray:
    """``min_h share_l[route_links[p, h]]`` over the hops with a link id
    ``>= 0``, ``+inf`` for a packet with none: f32 [P], bit for bit
    what the gather (``ref.eq3_bottleneck_ref``) gives."""
    n_l = share_l.shape[0]
    n_p = route_links.shape[0]
    rows = _round_up(max(-(-n_l // LANES), 1), ROWS)
    tab_t = jnp.pad(share_l.astype(jnp.float32),
                    (0, rows * LANES - n_l)).reshape(rows, LANES).T
    block = min(BLOCK, _round_up(max(n_p, 1), LANES))
    links_t = jnp.pad(route_links.astype(jnp.int32).T,
                      ((0, 0), (0, _round_up(n_p, block) - n_p)),
                      constant_values=-1)
    return bottleneck_lanes(tab_t, links_t, block=block,
                            interpret=interpret)[0, :n_p]
