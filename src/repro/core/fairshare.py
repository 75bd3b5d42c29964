"""Channel bandwidth allocation.

Paper Eq. 3 (fair share): every channel crossing link i gets l_bw(i)/nc(i);
a channel's rate is the minimum share along its route.  This is what
CloudSimSDN implements and what the paper's use-case uses.

Beyond paper: progressive-filling **max-min water-filling**, which is
Pareto-optimal (Eq. 3 can leave residual capacity on non-bottleneck links).
Offered as TRAFFIC_WATERFILL, used in the §Perf iterations of the advisor.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend import core as jex_core
from jax.interpreters import batching, mlir

from ..kernels.eq3_bottleneck.ref import eq3_bottleneck_ref

TRAFFIC_FAIRSHARE = 0  # paper Eq. 3
TRAFFIC_WATERFILL = 1  # beyond-paper max-min fairness

# Eq. 3 lookups lowered, by path (``lookup_counts``), and a trace-time
# switch for tests: the kernel through the Pallas interpreter on any
# platform (counted as ``kernel``)
_LOOKUPS = {"kernel": 0, "gather": 0}
_INTERPRET = False


def _count(x, *, path):
    _LOOKUPS[path] += 1
    return x


# identity on a lookup's result that counts its path where it is lowered
# (or run eagerly): a program keeps only the branch of the platform it is
# lowered for, so the count says which path the device runs
_counted_p = jex_core.Primitive("eq3_lookup")
_counted_p.def_impl(_count)
_counted_p.def_abstract_eval(lambda x, *, path: x)
batching.primitive_batchers[_counted_p] = lambda args, dims, *, path: (
    _counted_p.bind(*args, path=path), dims[0])
mlir.register_lowering(
    _counted_p, lambda ctx, x, *, path: [_count(x, path=path)])


def lookup_counts() -> dict:
    """Eq. 3 lookups lowered since import, by path: ``kernel`` (the
    VMEM-resident Pallas lookup, on the TPU) or ``gather``
    (``share_l[link]``, every other platform)."""
    return dict(_LOOKUPS)


def _gather(share_l, route_links):
    return _counted_p.bind(eq3_bottleneck_ref(share_l, route_links),
                           path="gather")


def _kernel(share_l, route_links, *, interpret=False):
    # imported here: Pallas adds ~1 s to the import of the whole package
    from ..kernels.eq3_bottleneck.ops import eq3_bottleneck
    return _counted_p.bind(
        eq3_bottleneck(share_l, route_links, interpret=interpret),
        path="kernel")


def bottleneck_share(share_l: jnp.ndarray,
                     route_links: jnp.ndarray) -> jnp.ndarray:
    """Each packet's smallest per-link share along its route, ``+inf``
    for an empty route.  The TPU serves an element gather one element at
    a time, so a program lowered for the TPU takes the Pallas kernel;
    every other platform keeps the gather.  Both give the same f32 bits
    (the same values are min-reduced)."""
    if _INTERPRET:
        return _kernel(share_l, route_links, interpret=True)
    return jax.lax.platform_dependent(share_l, route_links, tpu=_kernel,
                                      default=_gather)


def channel_counts(route_links: jnp.ndarray, active: jnp.ndarray,
                   n_links: int) -> jnp.ndarray:
    """nc(i): number of active channels crossing each directed link.

    route_links: int32 [N, H] link ids (-1 pad); active: bool [N]
    """
    mask = (route_links >= 0) & active[:, None]
    safe = jnp.maximum(route_links, 0)
    contrib = mask.astype(jnp.int32)
    return jnp.zeros((n_links,), jnp.int32).at[safe.reshape(-1)].add(
        contrib.reshape(-1))


def eq3_rates(route_links: jnp.ndarray, active: jnp.ndarray,
              link_bw: jnp.ndarray, intra_bw: float,
              nc: jnp.ndarray | None = None) -> jnp.ndarray:
    """Paper Eq. 3 rate for every packet (0 for inactive).

    Packets with an empty route (src host == dst host) move at ``intra_bw``.
    ``nc`` takes the per-link channel counts precomputed by the engine's
    fused network pass (DESIGN.md §8); ``None`` recomputes them here.
    """
    if nc is None:
        nc = channel_counts(route_links, active, link_bw.shape[0])
    # per-LINK share first (tiny link axis), then one lookup onto the
    # packet axis — same float op on the same operands as dividing after
    # the lookup, one packet-scale op cheaper (DESIGN.md §8)
    share_l = link_bw / jnp.maximum(nc, 1).astype(link_bw.dtype)
    bot = bottleneck_share(share_l, route_links)
    bot = jnp.where(jnp.isinf(bot), jnp.asarray(intra_bw, link_bw.dtype), bot)
    return jnp.where(active, bot, 0.0)


def waterfill_rates(route_links: jnp.ndarray, active: jnp.ndarray,
                    link_bw: jnp.ndarray, intra_bw: float,
                    n_iter: int | None = None) -> jnp.ndarray:
    """Progressive-filling max-min fair rates.

    Each iteration freezes every flow whose bottleneck link is globally
    saturated at the current fill level; at most n_links iterations needed.
    Fixed trip count for jit; early iterations simply become no-ops.
    """
    n_links = link_bw.shape[0]
    n_iter = n_iter if n_iter is not None else min(n_links, 32)
    valid = route_links >= 0
    safe = jnp.maximum(route_links, 0)

    def fill_level(alloc, frozen, live):
        """Per-flow fill level: min over the route of (link residual after
        frozen allocations) / (live flows on the link)."""
        used = jnp.zeros((n_links,), link_bw.dtype).at[safe.reshape(-1)].add(
            jnp.where(valid & frozen[:, None], alloc[:, None],
                      0.0).reshape(-1))
        resid = jnp.maximum(link_bw - used, 0.0)
        n_live = jnp.zeros((n_links,), jnp.int32).at[safe.reshape(-1)].add(
            (valid & live[:, None]).astype(jnp.int32).reshape(-1))
        share = resid / jnp.maximum(n_live, 1).astype(link_bw.dtype)
        share = jnp.where(n_live > 0, share, jnp.inf)
        return jnp.min(jnp.where(valid, share[safe], jnp.inf), axis=-1)

    def body(_, carry):
        alloc, frozen = carry
        live = active & ~frozen
        level = fill_level(alloc, frozen, live)  # [N]
        # global fill step: freeze flows bottlenecked at the minimum level
        glob = jnp.min(jnp.where(live, level, jnp.inf))
        glob = jnp.where(jnp.isinf(glob), 0.0, glob)
        hit = live & (level <= glob * (1 + 1e-6))
        alloc = jnp.where(hit, glob, alloc)
        frozen = frozen | hit
        return alloc, frozen

    alloc0 = jnp.zeros(route_links.shape[0], link_bw.dtype)
    frozen0 = jnp.zeros(route_links.shape[0], bool)
    alloc, frozen = jax.lax.fori_loop(0, n_iter, body, (alloc0, frozen0))
    # any still-unfrozen live flow (iter cap hit) gets its CURRENT fill
    # level: each link then carries at most n_live * (resid/n_live) = resid
    # on top of the frozen allocations — never oversubscribed.  (The old
    # fallback handed out Eq. 3 rates computed against the FULL link
    # capacity, stacking on top of frozen water-fill allocations and
    # exceeding shared links.)
    live = active & ~frozen
    alloc = jnp.where(live, fill_level(alloc, frozen, live), alloc)
    # intra-host flows
    empty = ~jnp.any(valid, axis=-1)
    alloc = jnp.where(active & empty, jnp.asarray(intra_bw, link_bw.dtype), alloc)
    return jnp.where(active, alloc, 0.0)


def rates(policy: jnp.ndarray, route_links: jnp.ndarray, active: jnp.ndarray,
          link_bw: jnp.ndarray, intra_bw: float,
          nc: jnp.ndarray | None = None) -> jnp.ndarray:
    """Dispatch on traffic policy (vmap-safe lax.cond).

    ``nc`` is the optional precomputed channel-count tensor for the Eq. 3
    branch (water-filling recomputes per-link live counts each fill
    iteration, so it has no use for a one-shot count).

    A host-static ``policy`` (a plain Python/numpy int — fleet cohorts,
    DESIGN.md §9) resolves the branch at trace time: under ``vmap`` a
    ``lax.cond`` on a batched predicate executes BOTH branches, and the
    32-iteration water-fill loop would tax every Eq.-3 step."""
    if isinstance(policy, (bool, int, np.integer)) or (
            isinstance(policy, np.ndarray) and policy.ndim == 0):
        if int(policy) == TRAFFIC_WATERFILL:
            return waterfill_rates(route_links, active, link_bw, intra_bw)
        return eq3_rates(route_links, active, link_bw, intra_bw, nc=nc)
    return jax.lax.cond(
        policy == TRAFFIC_WATERFILL,
        lambda: waterfill_rates(route_links, active, link_bw, intra_bw),
        lambda: eq3_rates(route_links, active, link_bw, intra_bw, nc=nc),
    )
