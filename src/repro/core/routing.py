"""SDN routing, TPU-adapted.

The paper's SDN controller runs Dijkstra per packet: shortest hop count first,
then (SDN mode) maximum bottleneck bandwidth among the equal-hop routes; legacy
mode picks one equal-hop route statically at random per src/dst flow.

Dijkstra is sequential pointer-chasing — the worst fit for a systolic array.
TPU adaptation (see DESIGN.md §2):

  1. *Offline* (setup, host-side numpy): hop distances via tropical (min-plus)
     matrix squaring — the same operation the Pallas kernel
     ``repro.kernels.tropical_apsp`` implements for on-device use — then
     enumeration of up to K equal-hop candidate routes per node pair from the
     shortest-path DAG.  Works for ANY topology (paper contribution 6).
  2. *Online* (inside the jitted event loop): route choice is a vectorized
     gather + masked-min + argmax over the K candidates — the controller's
     "global network view" is the live per-link channel-count tensor.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .topology import Topology

# ---------------------------------------------------------------------------
# offline: hop distances + candidate enumeration
# ---------------------------------------------------------------------------


def min_plus_square_np(d: np.ndarray) -> np.ndarray:
    """One tropical-semiring squaring step: d'[i,j] = min_k d[i,k] + d[k,j]."""
    return np.min(d[:, :, None] + d[None, :, :], axis=1)


def hop_distances_np(hop: np.ndarray) -> np.ndarray:
    """All-pairs hop distances by repeated min-plus squaring (O(log diam))."""
    d = hop.astype(np.float64)
    n = d.shape[0]
    steps = max(1, int(np.ceil(np.log2(max(2, n)))))
    for _ in range(steps):
        nd = min_plus_square_np(d)
        if np.array_equal(nd, d):
            break
        d = nd
    return d


@dataclasses.dataclass(frozen=True)
class RouteTable:
    """Padded candidate-route tensors for all node pairs.

    routes[p, k, h]  : link index of hop h of candidate k for pair p (-1 pad)
    n_cand[p]        : number of valid candidates for pair p (0 if unreachable
                       or src == dst)
    route_len[p, k]  : hops of candidate k
    max_hops, k_max  : static pad sizes
    truncated        : True if some pair had more equal-hop routes than k_max
    """

    routes: np.ndarray  # int32 [n_pairs, k_max, max_hops]
    n_cand: np.ndarray  # int32 [n_pairs]
    route_len: np.ndarray  # int32 [n_pairs, k_max]
    max_hops: int
    k_max: int
    n_nodes: int
    truncated: bool

    def pair(self, src: int, dst: int) -> int:
        return src * self.n_nodes + dst


def build_route_table(topo: Topology, k_max: int = 8,
                      max_hops: int | None = None) -> RouteTable:
    """Enumerate ALL equal-hop shortest routes (up to k_max) per node pair.

    An edge (u, v) lies on a shortest src->dst path iff
        dist(src, u) + 1 + dist(v, dst) == dist(src, dst)
    so the shortest-path DAG is read straight off the distance matrix and
    enumerated by DFS.  Host-side, runs once at setup.
    """
    with jax.profiler.TraceAnnotation("repro.front.routes"):
        n = topo.n_nodes
        dist = hop_distances_np(topo.hop_matrix())
        # adjacency list of directed links
        out_links: list[list[tuple[int, int]]] = [[] for _ in range(n)]
        for idx, (s, d) in enumerate(zip(topo.link_src, topo.link_dst)):
            out_links[int(s)].append((int(d), idx))

        finite = dist[np.isfinite(dist)]
        diam = int(finite.max()) if finite.size else 0
        mh = max_hops if max_hops is not None else max(1, diam)

        routes = np.full((n * n, k_max, mh), -1, dtype=np.int32)
        n_cand = np.zeros((n * n,), dtype=np.int32)
        route_len = np.zeros((n * n, k_max), dtype=np.int32)
        truncated = False

        for src in range(n):
            for dst in range(n):
                if src == dst or not np.isfinite(dist[src, dst]):
                    continue
                target = dist[src, dst]
                found: list[list[int]] = []
                stack: list[tuple[int, list[int]]] = [(src, [])]
                while stack and len(found) < k_max + 1:
                    node, path = stack.pop()
                    if node == dst:
                        found.append(path)
                        continue
                    for (nxt, lidx) in out_links[node]:
                        if dist[src, node] + 1 + dist[nxt, dst] == target:
                            stack.append((nxt, path + [lidx]))
                if len(found) > k_max:
                    truncated = True
                    found = found[:k_max]
                p = src * n + dst
                n_cand[p] = len(found)
                for k, f in enumerate(found):
                    route_len[p, k] = len(f)
                    routes[p, k, : len(f)] = f
    return RouteTable(routes=routes, n_cand=n_cand, route_len=route_len,
                      max_hops=mh, k_max=k_max, n_nodes=n, truncated=truncated)


# ---------------------------------------------------------------------------
# online: vectorized per-packet route choice (inside the event loop)
# ---------------------------------------------------------------------------

ROUTE_LEGACY = 0  # static equal-hop pick per (src,dst) flow  (paper §5.2)
ROUTE_SDN = 1     # per-packet max-bottleneck-bandwidth pick  (paper §5.2)


def candidate_bottleneck_bw(routes_k: jnp.ndarray, n_cand: jnp.ndarray,
                            link_bw: jnp.ndarray,
                            ch_count: jnp.ndarray) -> jnp.ndarray:
    """Available bottleneck bandwidth of each candidate if one more channel joins.

    routes_k : int32 [k_max, max_hops] link ids (-1 pad) for ONE pair
    returns  : f32 [k_max]  (-inf for invalid candidates)

    ``link_bw`` is the EFFECTIVE capacity: the engine zeroes dead links
    (DESIGN.md §7), so a candidate crossing an outage scores 0 and loses
    the argmax to any live route — the controller's global view includes
    link liveness for free.
    """
    links = routes_k  # [K, H]
    valid_hop = links >= 0
    safe = jnp.maximum(links, 0)
    # bandwidth this packet would see on each hop if it joined now
    hop_bw = link_bw[safe] / (ch_count[safe].astype(link_bw.dtype) + 1.0)
    hop_bw = jnp.where(valid_hop, hop_bw, jnp.inf)
    bot = jnp.min(hop_bw, axis=-1)  # [K]
    k_ids = jnp.arange(links.shape[0])
    return jnp.where(k_ids < n_cand, bot, -jnp.inf)


def sdn_route_choice(routes_k: jnp.ndarray, n_cand: jnp.ndarray,
                     link_bw: jnp.ndarray,
                     ch_count: jnp.ndarray) -> jnp.ndarray:
    """SDN pick for ONE pair: argmax of current bottleneck availability
    (Dijkstra objective #2).  Depends on the live channel counts, so the
    engine evaluates it inside the compacted ready-set scan — each
    activation sees the channels the controller just admitted."""
    bw = candidate_bottleneck_bw(routes_k, n_cand, link_bw, ch_count)
    return jnp.argmax(bw).astype(jnp.int32)


def legacy_route_choice(n_cand: jnp.ndarray,
                        flow_hash: jnp.ndarray) -> jnp.ndarray:
    """Legacy pick: deterministic hash of the flow id over the equal-hop
    set — fixed for the whole flow regardless of load.  Needs no channel
    feedback, so it vectorizes over any batch of pairs (DESIGN.md §8)."""
    return jnp.where(n_cand > 0, flow_hash % jnp.maximum(n_cand, 1),
                     0).astype(jnp.int32)


def choose_route(policy: jnp.ndarray, routes_k: jnp.ndarray,
                 n_cand: jnp.ndarray, link_bw: jnp.ndarray,
                 ch_count: jnp.ndarray, flow_hash: jnp.ndarray) -> jnp.ndarray:
    """Pick a candidate index for ONE pair per the active routing policy
    (see ``sdn_route_choice`` / ``legacy_route_choice``)."""
    return jnp.where(policy == ROUTE_SDN,
                     sdn_route_choice(routes_k, n_cand, link_bw, ch_count),
                     legacy_route_choice(n_cand, flow_hash)).astype(jnp.int32)


def flow_hash_u32(a: jnp.ndarray, b: jnp.ndarray, seed: jnp.ndarray) -> jnp.ndarray:
    """Counter-based integer hash (vmap-safe legacy 'random' route pick)."""
    x = (a.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
         ^ b.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
         ^ seed.astype(jnp.uint32) * jnp.uint32(0xC2B2AE3D))
    x = (x ^ (x >> 15)) * jnp.uint32(0x2C1B3C6D)
    x = (x ^ (x >> 12)) * jnp.uint32(0x297A2D39)
    x = x ^ (x >> 15)
    return x.astype(jnp.int32) & jnp.int32(0x7FFFFFFF)


# jnp APSP (used by tests & the roofline advisor for on-device distances; the
# Pallas kernel in repro.kernels.tropical_apsp is the TPU fast path)
def hop_distances_jnp(hop: jnp.ndarray, steps: int | None = None) -> jnp.ndarray:
    n = hop.shape[0]
    steps = steps if steps is not None else max(1, int(np.ceil(np.log2(max(2, n)))))

    def body(_, d):
        return jnp.min(d[:, :, None] + d[None, :, :], axis=1)

    return jax.lax.fori_loop(0, steps, body, hop)
