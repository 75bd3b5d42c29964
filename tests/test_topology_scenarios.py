"""Topology-builder invariants, workload generators, registry, and the
packed multi-topology sweep (single-run equivalence + smoke)."""
import numpy as np
import pytest

from simhelpers import tiny_setups as _tiny_setups
from repro.core import (PLACE_LEAST_USED, PLACE_RANDOM, PolicyConfig,
                        simulate)
from repro.core.routing import build_route_table, hop_distances_np
from repro.core.topology import GBPS, canonical_tree, fat_tree, leaf_spine
from repro.scenarios import (get_scenario, list_scenarios, sweep_grid,
                             uniform_workload, zipf_workload,
                             bursty_workload)

# ---------------------------------------------------------------------------
# builder invariants
# ---------------------------------------------------------------------------


def test_fat_tree_counts_and_full_bisection():
    k = 4
    topo = fat_tree(k)
    half = k // 2
    assert topo.n_hosts == k * half * half
    assert topo.n_switches == half * half + 2 * k * half
    # undirected cables: 3 layers of k*(k/2)^2 links + 1 SAN uplink
    assert topo.n_links == 2 * (3 * k * half * half + 1)
    # full (1:1) bisection: agg->core capacity equals total host capacity
    core_lo, core_hi = topo.n_hosts, topo.n_hosts + half * half
    is_core = lambda v: (core_lo <= v) & (v < core_hi)
    up = is_core(topo.link_dst) & ~is_core(topo.link_src) \
        & (topo.link_src != topo.storage(0))
    assert np.isclose(topo.link_bw[up].sum(), topo.n_hosts * GBPS)


def test_leaf_spine_counts_and_bisection_bw():
    s, l, h = 4, 4, 2
    topo = leaf_spine(n_spine=s, n_leaf=l, hosts_per_leaf=h)
    assert topo.n_hosts == l * h
    assert topo.n_switches == s + l
    assert topo.n_links == 2 * (s * l + l * h + 1)
    # bisection across a leaf split: every A->B host path crosses an
    # A-leaf -> spine link; cut capacity = (l/2) * s * fabric_bw
    leaf0 = topo.n_hosts + s
    a_leaves = np.arange(leaf0, leaf0 + l // 2)
    spines = np.arange(topo.n_hosts, topo.n_hosts + s)
    cut = np.isin(topo.link_src, a_leaves) & np.isin(topo.link_dst, spines)
    assert np.isclose(topo.link_bw[cut].sum(), (l // 2) * s * GBPS)


def _endpoint_n_cand(topo, rt):
    """Candidate counts between every pair of hosts and storage nodes."""
    ends = np.r_[np.arange(topo.n_hosts),
                 topo.storage(0) + np.arange(topo.n_storage)]
    return rt.n_cand_between(ends[:, None], ends[None, :])


def test_canonical_tree_structure_and_unique_routes():
    topo = canonical_tree(depth=3, fanout=2, hosts_per_edge=2)
    assert topo.n_switches == 1 + 2 + 4
    assert topo.n_hosts == 4 * 2
    # a tree has exactly one route between any two endpoints
    rt = build_route_table(topo, k_max=4)
    nc = _endpoint_n_cand(topo, rt)
    off = ~np.eye(nc.shape[0], dtype=bool)
    assert np.all(nc[off] == 1)


@pytest.mark.parametrize("topo_fn", [
    lambda: fat_tree(4),
    lambda: leaf_spine(3, 4, 2),
    lambda: canonical_tree(2, 3, 2),
])
def test_all_nodes_reachable_and_candidates_symmetric(topo_fn):
    topo = topo_fn()
    dist = hop_distances_np(topo.hop_matrix())
    assert np.all(np.isfinite(dist)), "fabric must be connected"
    rt = build_route_table(topo, k_max=16)
    nc = _endpoint_n_cand(topo, rt)
    # these fabrics are symmetric graphs: equal-hop route count must be too
    assert np.array_equal(nc, nc.T)
    assert np.all(nc[~np.eye(nc.shape[0], dtype=bool)] > 0)


def test_leaf_spine_route_diversity_equals_spine_count():
    s = 3
    topo = leaf_spine(n_spine=s, n_leaf=2, hosts_per_leaf=2)
    rt = build_route_table(topo, k_max=8)
    # inter-leaf host pair: one equal-hop route per spine
    assert rt.n_cand_between(0, topo.n_hosts - 1) == s
    # same-leaf host pair: single route via the shared leaf
    assert rt.n_cand_between(0, 1) == 1


# ---------------------------------------------------------------------------
# workload generators
# ---------------------------------------------------------------------------


def test_workloads_deterministic_and_well_formed():
    for gen in (uniform_workload, zipf_workload, bursty_workload):
        a, b = gen(n_jobs=5, seed=3), gen(n_jobs=5, seed=3)
        assert a == b, f"{gen.__name__} not deterministic"
        assert len(a) == 5
        for j in a:
            assert j.n_map >= 1 and j.n_reduce >= 1
            assert j.total_mi > 0 and j.input_gbits > 0
        assert all(x.submit_time <= y.submit_time for x, y in zip(a, a[1:]))
    assert uniform_workload(n_jobs=4, seed=0) != uniform_workload(n_jobs=4,
                                                                  seed=1)


def test_bursty_workload_gaps():
    jobs = bursty_workload(n_jobs=6, burst_size=3, burst_gap_s=100.0,
                           intra_gap_s=0.5)
    t = [j.submit_time for j in jobs]
    assert t[0] == 0.0 and t[2] == pytest.approx(1.0)
    assert t[3] == pytest.approx(100.0)  # second burst


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_registry_contents_and_overrides():
    names = list_scenarios()
    for required in ("paper-fabric", "fat-tree", "leaf-spine",
                     "canonical-tree"):
        assert required in names
    sc = get_scenario("leaf-spine", n_spine=2, n_leaf=2, hosts_per_leaf=2,
                      n_jobs=2)
    setup = sc.build()
    assert setup.cluster.topo.n_hosts == 4
    assert setup.n_jobs == 2
    with pytest.raises(KeyError):
        get_scenario("no-such-fabric")


# ---------------------------------------------------------------------------
# packed sweep: equivalence + smoke
# ---------------------------------------------------------------------------




def test_packed_sweep_matches_single_runs():
    """Padding/renumbering must not change any scenario's outcome."""
    scens = _tiny_setups()
    pols = [("least", PolicyConfig(placement=PLACE_LEAST_USED)),
            ("rand", PolicyConfig(placement=PLACE_RANDOM))]
    res = sweep_grid(scens, pols)
    t = np.asarray(res.states.time)
    assert t.shape == (4,)
    for si, (_, setup) in enumerate(scens):
        for pi, (_, pol) in enumerate(pols):
            single = simulate(setup, pol)
            assert not bool(single.stalled)
            packed_t = float(t[si * len(pols) + pi])
            assert packed_t == pytest.approx(float(single.time), rel=1e-5)


def test_simulate_scenarios_zipped_semantics():
    """Replica i of the zipped API runs consts[i] under pols[i]."""
    import jax.numpy as jnp
    from repro.core import simulate_scenarios
    from repro.scenarios import pack_setups, policy_arrays

    scens = _tiny_setups()
    consts, meta = pack_setups([s for _, s in scens])
    pols = {k: jnp.asarray(v) for k, v in policy_arrays(
        [PolicyConfig(placement=PLACE_LEAST_USED),
         PolicyConfig(placement=PLACE_RANDOM)]).items()}
    s = simulate_scenarios(consts, meta, pols)
    assert float(s.time[0]) == pytest.approx(float(simulate(
        scens[0][1], PolicyConfig(placement=PLACE_LEAST_USED)).time), rel=1e-5)
    assert float(s.time[1]) == pytest.approx(float(simulate(
        scens[1][1], PolicyConfig(placement=PLACE_RANDOM)).time), rel=1e-5)


def test_paper_fabric_scenario_matches_paper_setup():
    """The registered paper scenario must be the calibrated repro config."""
    from repro.core import paper_setup

    built = get_scenario("paper-fabric", seed=0, n_each=1).build()
    ref = paper_setup(seed=0, jobs=list(built.jobs))
    assert built.n_packets == ref.n_packets        # same split
    assert built.route_table.k_max == ref.route_table.k_max
    np.testing.assert_array_equal(built.route_table.n_cand,
                                  ref.route_table.n_cand)
    np.testing.assert_array_equal(built.pkt_bits, ref.pkt_bits)


def test_scenario_sweep_smoke():
    res = sweep_grid(_tiny_setups(),
                     [("least", PolicyConfig(placement=PLACE_LEAST_USED))])
    for row in res.rows():
        assert not row["stalled"], row
        assert np.isfinite(row["mean_completion_s"]), row
        assert row["mean_completion_s"] > 0
        assert row["energy_kwh"] > 0
        assert row["makespan_s"] >= row["mean_completion_s"] * 0.5
