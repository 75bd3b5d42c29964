"""Pure-jnp oracle for the Eq. 3 bottleneck lookup: the element gather
the engine runs on every backend but the TPU."""
from __future__ import annotations

import jax.numpy as jnp


def eq3_bottleneck_ref(share_l: jnp.ndarray,
                       route_links: jnp.ndarray) -> jnp.ndarray:
    """``min_h share_l[route_links[p, h]]`` over the hops with a link id
    ``>= 0``, ``+inf`` for a packet with none."""
    valid = route_links >= 0
    share = jnp.where(valid, share_l[jnp.maximum(route_links, 0)], jnp.inf)
    return jnp.min(share, axis=-1)
