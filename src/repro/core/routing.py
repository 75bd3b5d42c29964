"""SDN routing, TPU-adapted.

The paper's SDN controller runs Dijkstra per packet: shortest hop count first,
then (SDN mode) maximum bottleneck bandwidth among the equal-hop routes; legacy
mode picks one equal-hop route statically at random per src/dst flow.

Dijkstra is sequential pointer-chasing — the worst fit for a systolic array.
TPU adaptation (see DESIGN.md §2):

  1. *Offline* (setup, host-side numpy): packets travel only between
     endpoints (hosts and storage nodes), and every route of a single-homed
     endpoint starts (or ends) with its one link.  So the equal-hop
     candidates are enumerated per pair of *attachment* nodes — the switch
     a single-homed endpoint hangs from, else the endpoint itself — from
     breadth-first hop distances, up to K per pair in depth-first order; a
     packet's route is its uplink, the attachment pair's route, then its
     downlink.  Works for ANY topology (paper contribution 6).
  2. *Online* (inside the jitted event loop): route choice is a vectorized
     gather + masked-min + argmax over the K candidates — the controller's
     "global network view" is the live per-link channel-count tensor.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .topology import Topology

UNREACHABLE_HOPS = 1 << 20  # pair_hops sentinel: no candidate route

# ---------------------------------------------------------------------------
# offline: hop distances + candidate enumeration
# ---------------------------------------------------------------------------


def min_plus_square_np(d: np.ndarray) -> np.ndarray:
    """One tropical-semiring squaring step: d'[i,j] = min_k d[i,k] + d[k,j]."""
    return np.min(d[:, :, None] + d[None, :, :], axis=1)


def hop_distances_np(hop: np.ndarray) -> np.ndarray:
    """All-pairs hop distances by repeated min-plus squaring (O(log diam));
    the host reference of the Pallas kernel ``repro.kernels.tropical_apsp``.
    Builds an [n, n, n] array: small graphs only."""
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
    """Padded candidate routes between the attachments of the endpoints.

    node_att[v]        : attachment index of endpoint v; -1 where v is no
                         endpoint (a switch: no packet starts or ends there)
    node_up[v]         : link v -> its attachment; -1 where v is its own
    node_down[v]       : link attachment -> v; -1 likewise
    routes[a, b, k, h] : link of hop h of candidate k from attachment a to
                         b (-1 pad); the diagonal holds one empty route
    n_cand[a, b]       : valid candidates (0 where b is unreachable)
    pair_hops[a, b]    : hops of every candidate of (a, b), equal by
                         construction; UNREACHABLE_HOPS where none
    max_hops, k_max    : static pad sizes; max_hops is the longest
                         endpoint-to-endpoint route
    n_enumerated       : candidates kept over the off-diagonal pairs
    n_truncated        : pairs with more equal-hop routes than k_max
    """

    node_att: np.ndarray   # int32 [n_nodes]
    node_up: np.ndarray    # int32 [n_nodes]
    node_down: np.ndarray  # int32 [n_nodes]
    routes: np.ndarray     # int32 [n_att, n_att, k_max, max_hops]
    n_cand: np.ndarray     # int32 [n_att, n_att]
    pair_hops: np.ndarray  # int32 [n_att, n_att]
    max_hops: int
    k_max: int
    n_enumerated: int
    n_truncated: int

    @property
    def n_pairs(self) -> int:
        """Attachment pairs the table holds (diagonal included)."""
        return int(self.n_cand.size)

    def device_arrays(self) -> Dict[str, np.ndarray]:
        """The tensors the engine reads (``EngineConsts`` fields); per node
        one row ``node_route[v] = (node_att, node_up, node_down)``."""
        return {"routes": self.routes, "n_cand": self.n_cand,
                "pair_hops": self.pair_hops,
                "node_route": np.stack([self.node_att, self.node_up,
                                        self.node_down], axis=1)}

    @property
    def device_bytes(self) -> int:
        return sum(int(a.nbytes) for a in self.device_arrays().values())

    def n_cand_between(self, src, dst) -> np.ndarray:
        """Candidate count of node pairs (broadcast ``src`` x ``dst``):
        0 where src == dst, either is no endpoint, or no route exists."""
        src, dst = np.asarray(src), np.asarray(dst)
        a, b = self.node_att[src], self.node_att[dst]
        ok = (a >= 0) & (b >= 0) & (src != dst)
        return np.where(ok, self.n_cand[np.maximum(a, 0), np.maximum(b, 0)],
                        0).astype(np.int32)

    def candidates(self, src: int, dst: int) -> List[Tuple[int, ...]]:
        """The candidate routes of one node pair as link-id tuples, in the
        order the engine indexes them."""
        n = int(self.n_cand_between(src, dst))
        if n == 0:
            return []
        a, b = int(self.node_att[src]), int(self.node_att[dst])
        up = (int(self.node_up[src]),) if self.node_up[src] >= 0 else ()
        down = (int(self.node_down[dst]),) if self.node_down[dst] >= 0 else ()
        h = int(self.pair_hops[a, b])
        return [up + tuple(int(x) for x in self.routes[a, b, k, :h]) + down
                for k in range(n)]


def _attachments(topo: Topology) -> Tuple[np.ndarray, ...]:
    """(attachment node, uplink, downlink) of every node.  A host or
    storage node with exactly one link out and one in, both to the same
    switch, is attached to that switch through them; every other node is
    its own attachment (links -1)."""
    n = topo.n_nodes
    src = np.asarray(topo.link_src, np.int64)
    dst = np.asarray(topo.link_dst, np.int64)
    ids = np.arange(src.size)
    out_link = np.full(n, -1, np.int64)
    in_link = np.full(n, -1, np.int64)
    out_link[src] = ids
    in_link[dst] = ids
    nodes = np.arange(n)
    single = ((np.bincount(src, minlength=n) == 1)
              & (np.bincount(dst, minlength=n) == 1)
              & ~topo.is_switch(nodes))
    peer = np.where(single, dst[np.maximum(out_link, 0)], nodes)
    single &= (src[np.maximum(in_link, 0)] == peer) & topo.is_switch(peer)
    att = np.where(single, peer, nodes)
    up = np.where(single, out_link, -1)
    down = np.where(single, in_link, -1)
    return att, up, down


def _hops_to(topo: Topology, targets: np.ndarray) -> np.ndarray:
    """[len(targets), n_nodes] hop distance from every node TO each target
    (breadth-first over the reversed links, all targets at once);
    ``UNREACHABLE_HOPS`` where there is no path."""
    n = topo.n_nodes
    src = np.asarray(topo.link_src, np.int64)
    dst = np.asarray(topo.link_dst, np.int64)
    d = np.full((len(targets), n), UNREACHABLE_HOPS, np.int32)
    rows = np.arange(len(targets))
    d[rows, targets] = 0
    front = np.zeros((len(targets), n), bool)
    front[rows, targets] = True
    level = 0
    while front.any():
        r, l = np.nonzero(front[:, dst])      # links into the frontier
        nxt = np.zeros_like(front)
        nxt[r, src[l]] = True
        nxt &= d == UNREACHABLE_HOPS
        level += 1
        d[nxt] = level
        front = nxt
    return d


def build_route_table(topo: Topology, k_max: int = 8) -> RouteTable:
    """Enumerate the equal-hop shortest routes (up to k_max) between the
    attachments of the endpoints.

    With ``dist`` the hop distance to ``dst``, an edge (u, v) continues a
    shortest path to dst iff dist(v) == dist(u) - 1, so the shortest-path
    DAG is read off one breadth-first search per attachment.  Candidates
    come in the order of a depth-first search that pushes each node's
    links in order and pops the last pushed first; the paths from a node
    are shared by every source that passes through it.  A single-homed
    endpoint's search would visit its attachment's candidates in the same
    order, so composing uplink + attachment route + downlink gives the
    table an all-node-pairs search would.  Host-side, runs once at setup.
    """
    with jax.profiler.TraceAnnotation("repro.front.routes"):
        n = topo.n_nodes
        att, up, down = _attachments(topo)
        ends = np.r_[np.arange(topo.n_hosts),
                     topo.storage(0) + np.arange(topo.n_storage)]
        attach = np.unique(att[ends]).astype(np.int32)
        n_att = attach.size
        node_att = np.full(n, -1, np.int32)
        node_att[ends] = np.searchsorted(attach, att[ends])
        out_links: List[List[Tuple[int, int]]] = [[] for _ in range(n)]
        for idx, (s, d) in enumerate(zip(topo.link_src, topo.link_dst)):
            out_links[int(s)].append((int(d), idx))
        dist = _hops_to(topo, attach)

        cap = k_max + 1
        found: Dict[Tuple[int, int], list] = {}
        n_enum = n_trunc = 0
        for bi in range(n_att):
            dt = dist[bi].tolist()
            memo: Dict[int, list] = {int(attach[bi]): [()]}

            def paths(v: int) -> list:
                got = memo.get(v)
                if got is None:
                    got, want = [], dt[v] - 1
                    for nxt, lidx in reversed(out_links[v]):
                        if dt[nxt] == want:
                            got += [(lidx,) + p for p in paths(nxt)]
                            if len(got) >= cap:
                                del got[cap:]
                                break
                    memo[v] = got
                return got

            for ai in range(n_att):
                if ai == bi or dt[attach[ai]] == UNREACHABLE_HOPS:
                    continue
                p = paths(int(attach[ai]))
                n_trunc += len(p) > k_max
                found[ai, bi] = p[:k_max]
                n_enum += len(found[ai, bi])

        # the longest endpoint route: attachment route + up + down, over
        # distinct endpoints (two on one attachment share the empty route)
        n_up = np.zeros(n_att, np.int64)
        n_ep = np.zeros(n_att, np.int64)
        np.add.at(n_ep, node_att[ends], 1)
        np.add.at(n_up, node_att[ends], up[ends] >= 0)
        hang = (n_up > 0).astype(np.int64)
        pair_hops = np.full((n_att, n_att), UNREACHABLE_HOPS, np.int32)
        pair_hops[np.arange(n_att), np.arange(n_att)] = 0
        mh = 0
        for (ai, bi), p in found.items():
            if p:
                pair_hops[ai, bi] = len(p[0])
                mh = max(mh, len(p[0]) + hang[ai] + hang[bi])
        own = n_ep >= 2
        if own.any():
            mh = max(mh, int(np.minimum(n_up[own], 2).max()))
        mh = max(1, int(mh))

        routes = np.full((n_att, n_att, k_max, mh), -1, np.int32)
        n_cand = np.zeros((n_att, n_att), np.int32)
        n_cand[np.arange(n_att), np.arange(n_att)] = 1
        for (ai, bi), p in found.items():
            n_cand[ai, bi] = len(p)
            if p:
                routes[ai, bi, :len(p), :len(p[0])] = p
    return RouteTable(
        node_att=node_att,
        node_up=up.astype(np.int32), node_down=down.astype(np.int32),
        routes=routes, n_cand=n_cand, pair_hops=pair_hops, max_hops=mh,
        k_max=k_max, n_enumerated=n_enum, n_truncated=n_trunc)


# ---------------------------------------------------------------------------
# online: vectorized per-packet route choice (inside the event loop)
# ---------------------------------------------------------------------------

class RouteEnds(NamedTuple):
    """What the route of a node pair is composed from: its attachment
    pair ``(a, b)``, candidate count (0: unroutable, or src == dst) and
    the uplink / downlink (-1 where the endpoint is its own attachment),
    packed in one int32 array ``[..., 5]`` so that a scan reads one
    pair's with a single slice (``ends.at(i)``)."""

    packed: jnp.ndarray

    a = property(lambda e: e.packed[..., 0])
    b = property(lambda e: e.packed[..., 1])
    n_cand = property(lambda e: e.packed[..., 2])
    up = property(lambda e: e.packed[..., 3])
    down = property(lambda e: e.packed[..., 4])

    def at(self, i) -> "RouteEnds":
        return RouteEnds(self.packed[i])


def route_ends(c, src, dst) -> RouteEnds:
    """``RouteEnds`` of the node pairs ``(src, dst)``; ``c`` holds the
    ``RouteTable``'s device arrays (``EngineConsts``)."""
    rs, rd = c.node_route[src], c.node_route[dst]   # att, up, down
    a, b = rs[..., 0], rd[..., 0]
    ok = (a >= 0) & (b >= 0) & (src != dst)
    a, b = jnp.maximum(a, 0), jnp.maximum(b, 0)
    n = jnp.where(ok, c.n_cand[a, b], 0)
    return RouteEnds(jnp.stack([a, b, n, rs[..., 1], rd[..., 2]], axis=-1))


def _compose(mid, up, down, live):
    """Full routes ``[..., H]`` from attachment routes ``mid [..., H]``
    (-1 padded): the uplink (where ``up >= 0``), the middle hops, the
    downlink, then -1 padding; all -1 where not ``live``."""
    lead = mid.shape[:-1]
    up = jnp.broadcast_to(up, lead)[..., None]
    down = jnp.broadcast_to(down, lead)[..., None]
    n_mid = jnp.sum((mid >= 0).astype(jnp.int32), axis=-1, keepdims=True)
    has_up = up >= 0
    # the middle hops shifted one right behind an uplink (a route with an
    # uplink has at most H - 1 middle hops, so nothing falls off)
    shifted = jnp.concatenate([jnp.full(lead + (1,), -1, mid.dtype),
                               mid[..., :-1]], axis=-1)
    body = jnp.where(has_up, shifted, mid)
    m = jnp.arange(mid.shape[-1], dtype=jnp.int32) - has_up.astype(
        jnp.int32)
    out = jnp.where(m < 0, up, jnp.where(m < n_mid, body,
                                         jnp.where(m == n_mid, down, -1)))
    return jnp.where(jnp.broadcast_to(live, lead)[..., None], out, -1)


def route_links(c, ends: RouteEnds, cand) -> jnp.ndarray:
    """``[..., H]`` link ids (-1 pad) of candidate ``cand`` of each pair
    (all -1 where the pair has no such candidate)."""
    return _compose(c.routes[ends.a, ends.b, cand], ends.up, ends.down,
                    cand < ends.n_cand)


def route_candidates(c, ends: RouteEnds) -> jnp.ndarray:
    """``[K, H]`` link ids of every candidate of ONE pair."""
    mid = c.routes[ends.a, ends.b]
    live = jnp.arange(mid.shape[0], dtype=jnp.int32) < ends.n_cand
    return _compose(mid, ends.up, ends.down, live)


def node_pair_hops(c, src, dst) -> jnp.ndarray:
    """Hops of the candidates between nodes ``src`` and ``dst``: 0 where
    src == dst, ``UNREACHABLE_HOPS`` where no route exists."""
    rs, rd = c.node_route[src], c.node_route[dst]
    a, b = rs[..., 0], rd[..., 0]
    mid = c.pair_hops[jnp.maximum(a, 0), jnp.maximum(b, 0)]
    ok = (a >= 0) & (b >= 0) & (mid < UNREACHABLE_HOPS)
    hops = (mid + (rs[..., 1] >= 0).astype(jnp.int32)
            + (rd[..., 2] >= 0).astype(jnp.int32))
    return jnp.where(src == dst, 0,
                     jnp.where(ok, hops, UNREACHABLE_HOPS)).astype(jnp.int32)


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
