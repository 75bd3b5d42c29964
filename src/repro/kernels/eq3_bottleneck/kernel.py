"""Eq. 3 bottleneck lookup Pallas kernel — each packet's minimum link share.

Paper Eq. 3 gives a packet the smallest ``share[l]`` over the links ``l``
of its route.  As a gather that is one element read per (packet, hop),
and the TPU serves gathers of single elements one at a time.  Here the
per-link share table stays in VMEM and every lookup is dense vector work:

* link ``l`` lives at row ``l >> 7``, lane ``l & 127`` of the table laid
  out as ``[rows, 128]`` (passed transposed, ``[128, rows]``, so that
  packets run along the lanes of every intermediate);
* the row is picked by a 0/1 one-hot contraction on the MXU.  The f32
  table is split into three bf16 parts ``t1 + t2 + t3``; a product with
  1.0 or 0.0 is exact and each output sums one non-zero term, so
  ``(p1 + p2) + p3`` rebuilds every f32 share bit for bit;
* the lane is picked by a compare-select on the VPU, and the min over
  hops is taken in registers.  Only ``[P]`` floats go back to HBM.

The split is exact for 0 and for every finite share ``|t|`` in
``[2**-100, 2**127)``: the third part then stays a normal bf16 and the
first does not round to infinity.  Link ids of ``-1`` (route padding)
select nothing; a packet with no hop reads ``+inf``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SHIFT = 7           # log2(LANES): link l -> (row l >> 7, lane l & 127)


def _bottleneck_kernel(tab_ref, links_ref, o_ref):
    """One block of packets: ``tab_ref`` f32 [128, rows] (lane-major
    table), ``links_ref`` i32 [hops, bp], ``o_ref`` f32 [1, bp]."""
    tab = tab_ref[...]
    t1 = tab.astype(jnp.bfloat16)
    r1 = tab - t1.astype(jnp.float32)
    t2 = r1.astype(jnp.bfloat16)
    t3 = (r1 - t2.astype(jnp.float32)).astype(jnp.bfloat16)
    parts = jnp.concatenate([t1, t2, t3], axis=0)            # [3*128, rows]
    rows, bp = tab.shape[1], o_ref.shape[1]
    row_id = jax.lax.broadcasted_iota(jnp.int32, (rows, bp), 0)
    lane_id = jax.lax.broadcasted_iota(jnp.int32, (LANES, bp), 0)
    acc = jnp.full((LANES, bp), jnp.inf, jnp.float32)
    for h in range(links_ref.shape[0]):
        link = links_ref[pl.ds(h, 1), :]                     # [1, bp]
        hi = jnp.right_shift(link, SHIFT)                    # -1 -> no row
        lo = jnp.where(link >= 0, jnp.bitwise_and(link, LANES - 1), -1)
        onehot = jnp.where(row_id == hi, 1.0, 0.0).astype(jnp.bfloat16)
        got = jnp.dot(parts, onehot, preferred_element_type=jnp.float32)
        picked = (got[:LANES] + got[LANES:2 * LANES]) + got[2 * LANES:]
        acc = jnp.minimum(acc, jnp.where(lane_id == lo, picked, jnp.inf))
    o_ref[...] = jnp.min(acc, axis=0, keepdims=True)


def bottleneck_lanes(tab_t: jnp.ndarray, links_t: jnp.ndarray, *,
                     block: int, interpret: bool = False) -> jnp.ndarray:
    """``out[0, p] = min_h tab_t[l & 127, l >> 7]`` over the valid links
    ``l = links_t[h, p]`` (``+inf`` where none).  ``tab_t`` f32
    [128, rows] with ``rows`` a multiple of 16; ``links_t`` i32
    [hops, P] with ``P`` a multiple of ``block`` (itself of 128)."""
    rows = tab_t.shape[1]
    hops, n_p = links_t.shape
    return pl.pallas_call(
        _bottleneck_kernel,
        grid=(n_p // block,),
        in_specs=[pl.BlockSpec((LANES, rows), lambda i: (0, 0)),
                  pl.BlockSpec((hops, block), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n_p), jnp.float32),
        name="eq3_bottleneck",
        interpret=interpret,
    )(tab_t, links_t)
