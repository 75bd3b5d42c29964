"""Routing oracles: APSP vs Floyd-Warshall, candidate-route validity, and
the attachment-pair table against the plain all-pairs enumeration."""
import functools
import time

import numpy as np
import pytest

from repro.core.routing import build_route_table, hop_distances_np
from repro.core.topology import (canonical_tree, fat_tree, leaf_spine,
                                 paper_fat_tree, torus_2d)


def floyd_warshall(adj):
    d = adj.astype(np.float64).copy()
    n = d.shape[0]
    for k in range(n):
        d = np.minimum(d, d[:, k:k + 1] + d[k:k + 1, :])
    return d


def random_graph(n, m, seed):
    rng = np.random.RandomState(seed)
    adj = np.full((n, n), np.inf)
    np.fill_diagonal(adj, 0.0)
    for _ in range(m):
        i, j = rng.randint(0, n, 2)
        if i != j:
            adj[i, j] = 1.0
    return adj


@pytest.mark.parametrize("seed", range(5))
def test_hop_distances_vs_floyd_warshall(seed):
    adj = random_graph(24, 80, seed)
    got = hop_distances_np(adj.astype(np.float32))
    want = floyd_warshall(adj)
    finite = np.isfinite(want)
    assert np.array_equal(np.isfinite(got), finite)
    assert np.allclose(got[finite], want[finite])


def plain_dfs(topo, k_max):
    """The all-node-pairs route enumeration the attachment table replaced:
    per ordered endpoint pair, a depth-first search over the shortest-path
    DAG of the tropical distance matrix, keeping the first k_max routes."""
    n = topo.n_nodes
    dist = hop_distances_np(topo.hop_matrix())
    out_links = [[] for _ in range(n)]
    for idx, (s, d) in enumerate(zip(topo.link_src, topo.link_dst)):
        out_links[int(s)].append((int(d), idx))
    ends = endpoints(topo)
    table = {}
    for src in ends:
        for dst in ends:
            found = []
            if src != dst and np.isfinite(dist[src, dst]):
                target = dist[src, dst]
                stack = [(src, [])]
                while stack and len(found) < k_max + 1:
                    node, path = stack.pop()
                    if node == dst:
                        found.append(tuple(path))
                        continue
                    for nxt, lidx in out_links[node]:
                        if dist[src, node] + 1 + dist[nxt, dst] == target:
                            stack.append((nxt, path + [lidx]))
            table[src, dst] = found[:k_max]
    return table


def endpoints(topo):
    return list(range(topo.n_hosts)) + [topo.storage(i)
                                        for i in range(topo.n_storage)]


IDENTITY_TOPOS = {
    "paper_fat_tree": paper_fat_tree,
    "fat_tree4": lambda: fat_tree(4),
    "fat_tree8": lambda: fat_tree(8),
    "leaf_spine": lambda: leaf_spine(3, 4, 2),
    "canonical_tree": lambda: canonical_tree(3, 2, 2),
    "torus_2d": lambda: torus_2d(4, 4),
}


@functools.lru_cache(maxsize=None)
def _plain(name):
    topo = IDENTITY_TOPOS[name]()
    return topo, plain_dfs(topo, 64)


@pytest.mark.parametrize("k_max", [8, 16, 64])
@pytest.mark.parametrize("name", sorted(IDENTITY_TOPOS))
def test_attachment_routes_equal_plain_dfs(name, k_max):
    """Every host/SAN pair's composed candidates (uplink + attachment route
    + downlink) are the plain search's, element for element and in order;
    a search that stops after k_max + 1 routes keeps the prefix of one
    that stops after 65."""
    topo, plain = _plain(name)
    rt = build_route_table(topo, k_max=k_max)
    for (src, dst), want in plain.items():
        assert rt.candidates(src, dst) == want[:k_max], (src, dst)
    longest = max((len(r) for routes in plain.values() for r in routes),
                  default=1)
    assert rt.max_hops == longest


def test_fat_tree16_builds_at_full_path_diversity():
    """The k=16 fat-tree (1,345 nodes) builds at k_max = (k/2)^2 = 64 with
    no truncated pair and small device tensors."""
    topo = fat_tree(16)
    t0 = time.perf_counter()
    rt = build_route_table(topo, k_max=64)
    assert time.perf_counter() - t0 < 30.0
    assert rt.n_truncated == 0
    assert rt.device_bytes < 64 * 2**20
    assert rt.n_pairs == (128 + 1) ** 2      # edge switches + core 0
    assert rt.n_enumerated == 990_464
    # inter-pod hosts: every one of the 64 core paths; same pod: 8 aggs
    assert rt.n_cand_between(0, topo.n_hosts - 1) == 64
    assert rt.n_cand_between(0, 8) == 8
    assert rt.n_cand_between(0, 1) == 1


@pytest.mark.parametrize("topo_fn", [paper_fat_tree,
                                     lambda: fat_tree(4),
                                     lambda: torus_2d(4, 4)])
def test_route_table_paths_are_valid(topo_fn):
    topo = topo_fn()
    rt = build_route_table(topo, k_max=8)
    dist = hop_distances_np(topo.hop_matrix())
    src_l, dst_l = topo.link_src, topo.link_dst
    ends = endpoints(topo)
    checked = 0
    for src in ends[::max(1, len(ends) // 8)]:
        for dst in ends[::max(1, len(ends) // 8)]:
            for route in rt.candidates(src, dst):
                assert len(route) == int(dist[src, dst])   # shortest
                node = src
                for li in route:
                    assert li >= 0
                    assert int(src_l[li]) == node    # contiguous
                    node = int(dst_l[li])
                assert node == dst                   # reaches dst
                checked += 1
    assert checked > 0


def test_paper_topology_counts():
    topo = paper_fat_tree()
    assert topo.n_hosts == 16
    assert topo.n_switches == 20
    assert topo.n_storage == 1
    rt = build_route_table(topo, k_max=16)
    # SAN -> host: 2 parallel core-agg cables => 2 equal-hop routes
    assert rt.n_cand_between(topo.storage(0), 0) == 2
    # inter-pod host pair: 2 agg x 2 core x 2 parallel x 2 parallel = 16
    assert rt.n_cand_between(0, 4) == 16
    # same-edge pair: single route via the edge switch
    assert rt.n_cand_between(0, 1) == 1


def test_candidates_distinct():
    topo = paper_fat_tree()
    rt = build_route_table(topo, k_max=16)
    routes = rt.candidates(0, 4)
    assert len(routes) == 16
    assert len(set(routes)) == len(routes)
