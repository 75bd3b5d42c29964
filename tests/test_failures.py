"""Failure & recovery subsystem (DESIGN.md §7): §4 invariants under
failures, no-failure bit-identity, SDN-reroute vs legacy-pin semantics.

``mini_setup`` lives in conftest.py, ``with_failures`` / ``dims`` in
simhelpers.py (shared with the invariant and control-plane suites)."""
import jax.numpy as jnp
import numpy as np
import pytest

from simhelpers import dims, with_failures
from repro.core import (PolicyConfig, RECOVERY_RESUME, ROUTE_LEGACY,
                        ROUTE_SDN, host_crash, link_cut, no_failures,
                        simulate, simulate_batch, summarize)
from repro.core.flows import Flow, flows_setup
from repro.core.mapreduce import DONE
from repro.core.topology import leaf_spine, torus_2d


def test_all_inf_schedule_bit_identical(mini_setup):
    """The no-failure schedule IS the pre-failure engine, bitwise."""
    base = simulate(mini_setup, PolicyConfig(job_concurrency=2))
    inf = simulate(with_failures(mini_setup, no_failures(*dims(mini_setup))),
                   PolicyConfig(job_concurrency=2))
    for name in base._fields:
        a, b = np.asarray(getattr(base, name)), np.asarray(getattr(inf, name))
        assert np.array_equal(a, b, equal_nan=True), name


def test_conservation_and_clock_after_reexecution(mini_setup):
    """§4 invariants survive a host outage: every valid task/packet still
    completes, the clock stays monotone (finish >= start)."""
    sched = host_crash(*dims(mini_setup), host=0, at=30.0, recover_at=300.0)
    s = simulate(with_failures(mini_setup, sched),
                 PolicyConfig(job_concurrency=2))
    assert not bool(s.stalled)
    valid_t = np.asarray(mini_setup.task_valid)
    valid_p = np.asarray(mini_setup.pkt_valid)
    assert np.all(np.asarray(s.task_state)[valid_t] == DONE)
    assert np.all(np.asarray(s.pkt_state)[valid_p] == DONE)
    assert np.all(np.asarray(s.pkt_finish - s.pkt_start)[valid_p] >= -1e-5)
    assert float(s.time) > 0
    assert int(np.asarray(s.task_restarts).sum()) >= 1


def test_dead_host_draws_zero_power(mini_setup):
    """A permanently-dead host re-executes its tasks elsewhere and stops
    accumulating energy."""
    sched = host_crash(*dims(mini_setup), host=0, at=1.0)
    s = simulate(with_failures(mini_setup, sched),
                 PolicyConfig(job_concurrency=2))
    assert not bool(s.stalled)  # 15 other hosts absorb the work
    base = simulate(mini_setup, PolicyConfig(job_concurrency=2))
    # host 0 runs (almost) nothing after t=1 -> far below its healthy draw
    assert float(s.host_energy[0]) < float(base.host_energy[0])
    assert np.all(np.asarray(s.task_state)[np.asarray(mini_setup.task_valid)]
                  == DONE)


def test_stall_on_permanent_disconnect():
    """Cutting the only cable forever must stall, not free-transfer."""
    topo = torus_2d(2, 1, bw=1e9)
    setup = flows_setup(topo, [Flow(0, 1, 8.0)])
    sched = link_cut(topo.n_hosts, topo.n_links, [0, 1], at=2.0)
    s = simulate(with_failures(setup, sched), PolicyConfig())
    assert bool(s.stalled)
    assert float(s.time) == pytest.approx(2.0, rel=1e-5)


def test_transient_link_cut_resumes():
    """Same cut with a recovery instant: the flow finishes after repair."""
    topo = torus_2d(2, 1, bw=1e9)
    setup = flows_setup(topo, [Flow(0, 1, 8.0)])
    sched = link_cut(topo.n_hosts, topo.n_links, [0, 1], at=2.0,
                     recover_at=10.0)
    s = simulate(with_failures(setup, sched), PolicyConfig())
    assert not bool(s.stalled)
    # 2 s transferred, 8 s outage, 6 s remaining -> done at 16
    assert float(s.time) == pytest.approx(16.0, rel=1e-3)
    assert float(np.asarray(s.job_downtime).sum()) == pytest.approx(
        8.0, rel=1e-3)


def test_sdn_reroutes_legacy_pins():
    """The headline (DESIGN.md §7): on a path-diverse fabric SDN's global
    view routes around a cut; the legacy static hash can keep forwarding
    into it and waits out the outage."""
    topo = leaf_spine(2, 2, 2)
    setup = flows_setup(topo, [Flow(0, 2, 8.0)])
    times = {}
    for spine in (0, 1):
        cut = topo.links_touching(topo.switch(spine))
        sched = link_cut(topo.n_hosts, topo.n_links, cut, at=2.0,
                         recover_at=500.0)
        sf = with_failures(setup, sched)
        for name, pol in (("sdn", ROUTE_SDN), ("legacy", ROUTE_LEGACY)):
            s = simulate(sf, PolicyConfig(routing=pol))
            assert not bool(s.stalled)
            times[(name, spine)] = float(s.time)
    # whichever spine it was using, SDN finishes as if nothing happened
    assert min(times[("sdn", 0)], times[("sdn", 1)]) == pytest.approx(
        8.0, rel=1e-3)
    assert max(times[("sdn", 0)], times[("sdn", 1)]) == pytest.approx(
        8.0, rel=1e-3)
    # the legacy flow is pinned to exactly one spine: cutting THAT spine
    # parks it until recovery
    assert max(times[("legacy", 0)], times[("legacy", 1)]) > 100.0


def test_recovery_resume_not_slower_than_restart(mini_setup):
    """Checkpoint resume (beyond-paper) keeps task progress a restart
    would redo."""
    sched = host_crash(*dims(mini_setup), host=0, at=50.0, recover_at=400.0)
    sf = with_failures(mini_setup, sched)
    restart = simulate(sf, PolicyConfig(job_concurrency=2))
    resume = simulate(sf, PolicyConfig(job_concurrency=2,
                                       recovery=RECOVERY_RESUME))
    assert not bool(restart.stalled) and not bool(resume.stalled)
    assert float(resume.time) <= float(restart.time) + 1e-3


def test_batch_single_bit_equality_with_failures(mini_setup):
    """§4: a vmapped policy batch equals the corresponding single runs,
    failures included."""
    sched = host_crash(*dims(mini_setup), host=2, at=40.0, recover_at=200.0)
    sf = with_failures(mini_setup, sched)
    pols = {"routing": jnp.asarray([ROUTE_SDN, ROUTE_LEGACY]),
            "job_concurrency": jnp.asarray([2, 2])}
    sb = simulate_batch(sf, pols)
    for i, routing in enumerate((ROUTE_SDN, ROUTE_LEGACY)):
        si = simulate(sf, PolicyConfig(routing=routing, job_concurrency=2))
        assert float(sb.time[i]) == float(si.time)
        assert np.array_equal(np.asarray(sb.task_restarts[i]),
                              np.asarray(si.task_restarts))


def test_total_outage_defers_admission(mini_setup):
    """With EVERY host dead at release time the ResourceManager has
    nowhere to place: admission waits for the first recovery breakpoint
    instead of piling tasks onto a dead VM slot."""
    n_h, n_l = dims(mini_setup)
    sched = no_failures(n_h, n_l)
    sched.host_fail_t[:] = 0.0
    sched.host_recover_t[:] = 50.0
    s = simulate(with_failures(mini_setup, sched),
                 PolicyConfig(job_concurrency=2))
    assert not bool(s.stalled)
    admit = np.asarray(s.job_admit_t)
    assert np.nanmin(admit) >= 50.0  # nothing admitted while all-dead
    assert np.all(np.asarray(s.task_state)[np.asarray(mini_setup.task_valid)]
                  == DONE)


def test_recovery_metrics_in_report(mini_setup):
    sched = host_crash(*dims(mini_setup), host=0, at=30.0, recover_at=300.0)
    s = simulate(with_failures(mini_setup, sched),
                 PolicyConfig(job_concurrency=2))
    rep = summarize(mini_setup, s)
    for key in ("task_reexecs", "pkt_reroutes", "downtime_s"):
        assert key in rep and rep[key].shape == (mini_setup.n_jobs,)
    assert int(rep["task_reexecs"].sum()) == \
        int(np.asarray(s.task_restarts).sum())


def test_experiment_failure_axis(mini_setup):
    """Experiment(failures=...) replicates scenarios per schedule and the
    whole grid runs as one program with recovery metrics in rows()."""
    from repro.api import Experiment
    from repro.scenarios.failures import failure_injector
    res = Experiment(
        scenarios=("mini", mini_setup),
        policies=[("sdn", PolicyConfig(routing=ROUTE_SDN,
                                       job_concurrency=2)),
                  ("legacy", PolicyConfig(routing=ROUTE_LEGACY,
                                          job_concurrency=2))],
        failures=[("none", no_failures(*dims(mini_setup))),
                  ("r1", failure_injector(host_rate=3e-4, link_rate=3e-4,
                                          mttr=120.0, horizon=2000.0,
                                          seed=1))],
    ).run()
    assert res.n_scenarios == 2 and res.n_policies == 2
    rows = res.rows()
    assert len(rows) == 4
    for row in rows:
        assert not row["stalled"]
        assert {"task_reexecs", "pkt_reroutes", "downtime_s"} <= set(row)
    # the all-inf cell reports zero recovery activity
    none_rows = [r for r in rows if r["scenario"].endswith("none")]
    assert none_rows and all(r["task_reexecs"] == 0 and r["pkt_reroutes"] == 0
                             for r in none_rows)


def test_failed_scenario_registry_entries():
    from repro.scenarios import get_scenario
    sc = get_scenario("paper-fabric-failures", n_each=1)
    setup = sc.build()
    assert setup.failures is not None and setup.failures.any_failures
    sc2 = get_scenario("leaf-spine-failures")
    setup2 = sc2.build()
    assert setup2.failures is not None
    # link cuts are drawn per CABLE: both directed slots agree
    lf = setup2.failures.link_fail_t
    assert np.array_equal(lf[0::2], lf[1::2], equal_nan=True)
