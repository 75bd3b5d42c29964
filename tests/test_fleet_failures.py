"""Failure transitions in the fleet chunk (DESIGN.md §9).

The chunk decides the transitions' predicate, "some live lane has a
new death", once for all its lanes outside its lane vmap, so their
``lax.cond`` stays a cond.  ``run_fleet`` stays bit-identical to
``Experiment.run`` across host and link outages, both recovery modes,
lanes that meet a death on different steps, a lane that finishes exactly
on a fail instant, mid-cohort refills and a rate-0 point; ``FleetStats``
counts the chunk steps that took the transitions."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from simhelpers import assert_states_equal, dims
from repro.api import Experiment, fleet
from repro.core import engine
from repro.core.failures import no_failures
from repro.core.policies import (RECOVERY_RESTART, RECOVERY_RESUME,
                                 ROUTE_LEGACY, ROUTE_SDN, PolicyConfig,
                                 as_policy_arrays)
from repro.core.streaming import RingSpec, ring_setup
from repro.scenarios import get_scenario
from repro.scenarios.arrivals import TraceArrivals

# two routing cohorts of four: both recovery modes, and job concurrency 1
# (serialized, long) beside 3 (short), so lanes of one cohort reach a
# death on different steps and finish at different times
POLICIES = [PolicyConfig(routing=r, recovery=rc, job_concurrency=jc)
            for r in (ROUTE_LEGACY, ROUTE_SDN)
            for rc in (RECOVERY_RESTART, RECOVERY_RESUME)
            for jc in (1, 3)]
WIDTH = 3          # < 4 members per cohort: a lane refills mid-cohort


def _schedule(n_h, n_l, hosts=(), links=()):
    """Outages ``(device, fail_t, recover_t)`` for hosts and links."""
    s = no_failures(n_h, n_l)
    for fail, rec, rows in ((s.host_fail_t, s.host_recover_t, hosts),
                            (s.link_fail_t, s.link_recover_t, links)):
        for i, at, back in rows:
            fail[i], rec[i] = at, back
    return s.validate(n_h, n_l)


def _assert_identical(serial, fl, label):
    for i in range(len(serial.scenario_names)):
        for p in range(len(serial.policy_names)):
            assert_states_equal(serial.state(i, p), fl.state(i, p),
                                f"{label} {serial.scenario_names[i]}/{p}")


@pytest.fixture(scope="module")
def edge(mini_setup):
    """A host death at the exact instant a short lane (concurrency 3)
    finishes, while the long lanes of its cohort still run."""
    n_h, n_l = dims(mini_setup)
    healthy = Experiment(scenarios=("mini", mini_setup),
                         policies=POLICIES).run()
    t_end = float(np.asarray(healthy.states.time)[0, 1])
    assert np.asarray(healthy.states.time)[0, 0] > t_end
    return t_end, _schedule(n_h, n_l, hosts=[(2, t_end, t_end + 20.0)])


def test_fleet_identical_on_failure_grid(mini_setup, edge):
    """Host outages, link outages, the finish-on-a-fail-instant point and
    a rate-0 point, packed into one grid and drained through 3 lanes."""
    n_h, n_l = dims(mini_setup)
    t_end, edge_sched = edge
    points = [
        ("none", no_failures(n_h, n_l)),
        ("hosts", _schedule(n_h, n_l, hosts=[
            (0, 150.0, 400.0), (1, 600.0, 700.0), (4, 1100.0, 1300.0),
            (9, 2000.0, np.inf)])),
        ("links", _schedule(n_h, n_l, links=[
            (li, 100.0 + 37.0 * li, 300.0 + 37.0 * li)
            for li in range(0, n_l, 3)])),
        ("edge", edge_sched),
    ]
    exp = Experiment(scenarios=("mini", mini_setup), policies=POLICIES,
                     failures=points)
    serial = exp.run()
    fl, stats = exp.run_fleet(width=WIDTH, chunk_steps=4,
                              return_stats=True)
    _assert_identical(serial, fl, "failure grid:")
    assert stats.refills > 0
    assert 0 < stats.fail_steps <= stats.chunk_steps
    st = serial.states
    names = list(serial.scenario_names)
    hosts, links, edge_i = (names.index(f"mini/{n}")
                            for n in ("hosts", "links", "edge"))
    # the transitions acted: tasks restarted, packets rerouted
    assert np.asarray(st.task_restarts)[hosts].sum() > 0
    assert np.asarray(st.pkt_reroutes)[links].sum() > 0
    # the short lane ended ON the fail instant with the death unseen,
    # while the long lane of its cohort ran past it
    time = np.asarray(st.time)[edge_i]
    assert time[1] == np.float32(t_end)
    assert not np.asarray(st.host_dead)[edge_i, 1].any()
    assert time[0] > t_end


@pytest.mark.parametrize("point", ["rate-0", "after-the-end"])
def test_fail_steps_zero_without_a_death(mini_setup, point):
    """No lane meets a death: with no schedule at all (the chunk has no
    failure block), and with one whose only outage comes after every sim
    has finished (the hoisted block is built but never fires)."""
    n_h, n_l = dims(mini_setup)
    sched = (no_failures(n_h, n_l) if point == "rate-0"
             else _schedule(n_h, n_l, hosts=[(3, 1e6, 2e6)]))
    exp = Experiment(scenarios=("mini", mini_setup), policies=POLICIES,
                     failures=[(point, sched)])
    assert exp.build()[1].has_failures is (point != "rate-0")
    fl, stats = exp.run_fleet(width=WIDTH, chunk_steps=4,
                              return_stats=True)
    _assert_identical(exp.run(), fl, f"{point}:")
    assert stats.fail_steps == 0
    assert stats.chunk_steps >= int(np.asarray(fl.states.steps).max())


@pytest.mark.parametrize("lane0_done", [True, False])
def test_done_lane_is_masked_out_of_the_predicate(mini_setup, edge,
                                                  lane0_done):
    """Lane 0 sits on a fail instant with stale masks, so its refresh
    reports a new death; lane 1 is at t=0.  One chunk step fires only if
    lane 0 is live."""
    t_end, sched = edge
    consts, meta = engine.make_consts(dataclasses.replace(mini_setup,
                                                          failures=sched))
    chunk = jax.jit(engine.make_fleet_chunk(
        meta, {f: 0 for f in fleet.STATIC_FIELDS}, chunk_steps=1))
    s, cache, done = engine.init_fleet_carry(consts, meta, 2)
    s = s._replace(time=s.time.at[0].set(t_end))
    done = done.at[0].set(lane0_done)
    pol = {k: jnp.broadcast_to(v, (2,))
           for k, v in as_policy_arrays(None).items()
           if k not in fleet.STATIC_FIELDS}
    _, counts = chunk(consts, pol, (s, cache, done))
    assert np.asarray(counts).tolist() == [1, 0 if lane0_done else 1]


def test_stream_with_host_and_link_failures():
    """``run_stream`` drives the same chunk: on a trace that fits the
    ring it reproduces ``Experiment.run`` bitwise under host and link
    outages, and its chunks took the failure transitions."""
    setup = get_scenario("paper-fabric-failures", split=1,
                         host_rate=2e-3, link_rate=2e-3,
                         horizon=2000.0).build()
    arrivals = TraceArrivals(jobs=tuple(setup.jobs))
    jobs = [a.job for a in arrivals.events(1e9)]
    pols = [("sdn", PolicyConfig(routing=ROUTE_SDN, job_concurrency=2)),
            ("legacy", PolicyConfig(routing=ROUTE_LEGACY,
                                    recovery=RECOVERY_RESUME))]
    exp = Experiment(scenarios=("pff", setup), policies=pols)
    res = exp.run_stream(arrivals, 1e9, slots=len(jobs),
                         return_states=True)
    assert res.stats.refills == 0
    assert 0 < res.stats.fail_steps <= res.stats.chunk_steps
    spec = RingSpec.for_jobs(jobs, slots=len(jobs))
    rs = ring_setup(jobs, setup.cluster, spec, route_table=setup.route_table,
                    failures=setup.failures)
    ref = Experiment(scenarios=("ring", rs), policies=pols).run()
    for pi, (name, _) in enumerate(pols):
        assert_states_equal(ref.state(0, pi), res.final_states[pi], name)
