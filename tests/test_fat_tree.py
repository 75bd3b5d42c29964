"""The Al-Fares k-ary fat-tree deployment (``al-fares-fat-tree``): the
engine over the attachment-pair route table against the benchmark's plain
event-loop reference (``bench/harness/reference.py``, which enumerates its
own routes per node pair), and against the same engine fed a table from
the plain all-pairs search."""
import dataclasses
import sys
from pathlib import Path

import numpy as np
import pytest

from conftest import assert_states_equal
from repro.api import Experiment, PolicyConfig
from repro.core.routing import RouteTable, UNREACHABLE_HOPS
from repro.scenarios import get_scenario
from test_routing import endpoints, plain_dfs

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))
from harness import compare, reference, scenario as bench_scenario  # noqa: E402
from generators.common import fetch, sim_leaves  # noqa: E402

SEEDS = (3, 1234567891)


@pytest.mark.parametrize("k, n_each, k_max", [(4, 1, 4), (8, 2, 16)])
def test_fleet_matches_the_plain_reference(k, n_each, k_max):
    """SDN and legacy, two seeds each, through ``run_fleet``: every
    simulation finishes and is within the fat-tree cell's limits."""
    cfg = {"registry": "al-fares-fat-tree", "job_concurrency": 1_000_000,
           "scenario": {"k": k, "n_each": n_each, "split": 2,
                        "k_max": k_max}}
    pols = [(f"{r}/{s}", PolicyConfig(routing=r, seed=s))
            for r in (0, 1) for s in SEEDS]
    exp = Experiment(scenarios=bench_scenario.scenario(cfg), policies=pols)
    host = fetch(exp.run_fleet(width=2, chunk_steps=64).states)
    (_, setup), = exp.scenarios
    assert setup.route_table.n_truncated == 0
    sc = bench_scenario.plain(setup, cfg)
    per_sim = []
    for p, (_, pol) in enumerate(pols):
        leaves = sim_leaves(host, 0, p)
        assert not leaves["stalled"]
        ref = reference.simulate(sc, int(pol.routing), int(pol.seed))
        per_sim.append(compare.gaps(leaves, ref))
    worst = compare.worst(per_sim)
    assert worst["stalls"] == 0
    assert compare.judge(worst, compare.load_limits("fattree16-fleet")), worst


def _plain_table(topo, like: RouteTable) -> RouteTable:
    """The table of the plain all-node-pairs search in the engine's
    layout: every endpoint its own attachment, no uplinks to compose."""
    ends = np.asarray(endpoints(topo), np.int32)
    table = plain_dfs(topo, like.k_max)
    n_e = ends.size
    routes = np.full((n_e, n_e, like.k_max, like.max_hops), -1, np.int32)
    n_cand = np.eye(n_e, dtype=np.int32)
    hops = np.where(np.eye(n_e, dtype=bool), 0,
                    UNREACHABLE_HOPS).astype(np.int32)
    for i, src in enumerate(ends):
        for j, dst in enumerate(ends):
            found = table[int(src), int(dst)]
            if found:
                n_cand[i, j] = len(found)
                hops[i, j] = len(found[0])
                routes[i, j, :len(found), :len(found[0])] = found
    node_att = np.full(topo.n_nodes, -1, np.int32)
    node_att[ends] = np.arange(n_e)
    none = np.full(topo.n_nodes, -1, np.int32)
    return RouteTable(node_att=node_att, node_up=none,
                      node_down=none, routes=routes, n_cand=n_cand,
                      pair_hops=hops, max_hops=like.max_hops,
                      k_max=like.k_max, n_enumerated=int(n_cand.sum() - n_e), n_truncated=0)


@pytest.mark.parametrize("name", ["paper-fabric", "paper-fabric-failures",
                                  "paper-fabric-ctrl"])
def test_final_states_equal_those_of_the_plain_table(name):
    """Composed routes (uplink + attachment route + downlink) against the
    plain search's node-pair routes fed to the same engine: the final
    states are bit-identical, under SDN and legacy, outages (route
    intersection) and the controller with migration (hop estimates)."""
    setup = get_scenario(name, n_each=2).build()
    plain = dataclasses.replace(setup, route_table=_plain_table(
        setup.cluster.topo, setup.route_table))
    pols = [PolicyConfig(routing=r, seed=s) for r in (0, 1) for s in SEEDS]
    if name == "paper-fabric-ctrl":
        pols += [PolicyConfig(routing=1, seed=5, migration=1),
                 PolicyConfig(routing=1, seed=5, install_mode=1,
                              migration=1)]
    got = Experiment(scenarios=setup, policies=pols).run().states
    want = Experiment(scenarios=plain, policies=pols).run().states
    assert_states_equal(got, want, name)


def test_k16_deployment_lowers_to_the_configured_backlog():
    """k = 16, 56 jobs of each class: 168 jobs, 1,008 tasks for 1,024 VMs,
    5,152 packets; 64 routes between pods, none truncated."""
    setup = get_scenario("al-fares-fat-tree", k=16, n_each=56, split=2,
                         k_max=64).build()
    topo = setup.cluster.topo
    assert (topo.n_hosts, topo.n_switches, topo.n_nodes, topo.n_links) == (
        1024, 320, 1345, 6146)
    assert (setup.n_jobs, setup.n_tasks, setup.n_packets) == (168, 1008,
                                                              5152)
    rt = setup.route_table
    assert rt.n_truncated == 0
    assert rt.n_cand_between(0, topo.n_hosts - 1) == 64
    assert rt.device_bytes <= 64 * 2**20
