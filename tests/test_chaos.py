"""Gray failures, straggler speculation & controller failover
(DESIGN.md §13).

Covers: degradation-schedule semantics (exact rate arithmetic at the
piecewise boundary), the straggler-speculation win path and its off-state
inertness, controller failover accounting, the ``FailureSchedule`` /
``DegradationSchedule`` validation rejections, ``check_finite``
falsifiability, and the full chaos composition (outages x degradation x
failover x speculation) streamed through ``run_stream``.  The §13
OFF-switch bit-identity against the reference kernel lives in
test_engine_equiv.py; the all-unity-factor hypothesis property in
test_chaos_property.py.
"""
import dataclasses

import numpy as np
import pytest

from simhelpers import assert_states_equal, dims, with_ctrl, with_degradation
from invariants import check_all, check_chaos, check_finite, check_stream
from repro.core import (PolicyConfig, host_crash, host_slowdown,
                        link_brownout, no_degradation, no_failures, simulate)
from repro.core.ctrlplane import CtrlPlaneConfig
from repro.core.engine import make_consts
from repro.core.flows import Flow, flows_setup
from repro.core.mapreduce import build_setup
from repro.core.policies import (PLACE_ROUND_ROBIN, ROUTE_SDN, SPEC_OFF,
                                 SPEC_ON)
from repro.core.topology import leaf_spine, torus_2d
from repro.scenarios import get_scenario, make_cluster, uniform_workload


# ---------------------------------------------------------------------------
# validation (the satellite bugfix + the new schedule's rejections)
# ---------------------------------------------------------------------------


def test_failure_validate_rejects_zero_length_window():
    """Regression: ``recover_t <= fail_t`` used to slip through validate
    silently (the window never fired); now it is a hard error."""
    sched = no_failures(4, 8)
    sched.host_fail_t[1] = 10.0
    sched.host_recover_t[1] = 10.0      # zero-length
    with pytest.raises(ValueError, match="recover_t <= fail_t"):
        sched.validate(4, 8)
    sched = no_failures(4, 8)
    sched.link_fail_t[3] = 5.0
    sched.link_recover_t[3] = 2.0       # negative-length
    with pytest.raises(ValueError, match="recover_t <= fail_t"):
        sched.validate(4, 8)


def test_degradation_validate_rejections():
    s = no_degradation(4, 8)
    s.host_slow_t[0] = 10.0
    s.host_restore_t[0] = 10.0
    s.host_factor[0] = 0.5
    with pytest.raises(ValueError, match="restore_t <= slow_t"):
        s.validate(4, 8)
    s = no_degradation(4, 8)
    s.link_slow_t[2] = 1.0
    s.link_factor[2] = 0.0              # a zero factor is an outage, not
    with pytest.raises(ValueError):     # a gray window
        s.validate(4, 8)
    s = no_degradation(4, 8)
    s.host_slow_t[1] = 1.0
    s.host_factor[1] = np.inf
    with pytest.raises(ValueError):
        s.validate(4, 8)
    with pytest.raises(AssertionError, match="shape"):
        no_degradation(4, 8).validate(5, 8)


# ---------------------------------------------------------------------------
# degradation semantics
# ---------------------------------------------------------------------------


def test_link_brownout_exact_piecewise_rate():
    """A factor-0.5 brownout from t=2 on the only cable: 2 s at full rate,
    the remaining 6 units at half rate -> done at 14.  The analytic dt-min
    must hit the t=2 boundary exactly (deg_breaks joins the min)."""
    topo = torus_2d(2, 1, bw=1e9)
    setup = flows_setup(topo, [Flow(0, 1, 8.0)])
    sched = link_brownout(topo.n_hosts, topo.n_links, [0, 1], at=2.0,
                          factor=0.5)
    s = simulate(with_degradation(setup, sched), PolicyConfig())
    assert not bool(s.stalled)
    assert float(s.time) == pytest.approx(14.0, rel=1e-3)
    assert float(s.degraded_time) == pytest.approx(12.0, rel=1e-3)
    # restoring at t=6 gives 2 full + 4*0.5=2 browned + 4 full -> 10
    sched2 = link_brownout(topo.n_hosts, topo.n_links, [0, 1], at=2.0,
                           factor=0.5, restore_at=6.0)
    s2 = simulate(with_degradation(setup, sched2), PolicyConfig())
    assert float(s2.time) == pytest.approx(10.0, rel=1e-3)
    assert float(s2.degraded_time) == pytest.approx(4.0, rel=1e-3)


def test_host_slowdown_stretches_compute(mini_setup):
    """Halving every host's MIPS from t=0 stretches the makespan and the
    whole run counts as degraded time."""
    n_h, n_l = dims(mini_setup)
    sched = no_degradation(n_h, n_l)
    sched.host_slow_t[:] = 0.0
    sched.host_factor[:] = 0.5
    base = simulate(mini_setup, PolicyConfig(job_concurrency=2))
    slow = simulate(with_degradation(mini_setup, sched.validate(n_h, n_l)),
                    PolicyConfig(job_concurrency=2))
    assert not bool(slow.stalled)
    assert float(slow.time) > float(base.time)
    assert float(slow.degraded_time) == pytest.approx(float(slow.time),
                                                      rel=1e-5)
    consts, meta = make_consts(
        with_degradation(mini_setup, sched.validate(n_h, n_l)))
    check_all(consts, meta, slow, label="host-slowdown")


def test_unity_factor_schedule_bit_identical(mini_setup):
    """An attached all-factor-1.0 schedule is structurally OFF: its
    windows are inert, ``has_degradation`` stays False, and the run is
    bitwise the no-schedule program."""
    n_h, n_l = dims(mini_setup)
    sched = no_degradation(n_h, n_l)
    sched.host_slow_t[:] = 3.0          # windows exist, but factor == 1.0
    sched.host_restore_t[:] = 9.0
    assert not sched.validate(n_h, n_l).any_degradation
    base = simulate(mini_setup, PolicyConfig(job_concurrency=2))
    unit = simulate(with_degradation(mini_setup, sched.validate(n_h, n_l)),
                    PolicyConfig(job_concurrency=2))
    assert_states_equal(base, unit, "unity-factor")


# ---------------------------------------------------------------------------
# straggler speculation
# ---------------------------------------------------------------------------


def _straggler_setup(spec_slots):
    """4-host leaf-spine, host 0 crawling at 5% MIPS from t=0: with
    round-robin placement and 8-wide map waves some tasks land on host 0
    and crawl while healthy peers expose them — the textbook straggler.
    (Detection is rate-vs-live-job-median, so the wide template matters:
    a straggler whose peers have all finished is undetectable.)"""
    from repro.scenarios.workloads import JobTemplate
    topo = leaf_spine(2, 2, 2)
    cluster = make_cluster(topo)
    sched = host_slowdown(topo.n_hosts, topo.n_links, host=0, at=0.0,
                          factor=0.05)
    # 6 maps round-robin over 4 VMs puts exactly 2 maps on the slow host
    # and the 2 reduces on healthy vm2/vm3 — the crawling maps are the
    # critical path AND keep healthy peers alive long enough to be seen
    template = JobTemplate(n_map=6, n_reduce=2)
    return build_setup(uniform_workload(n_jobs=1, seed=0, template=template),
                       cluster, degradation=sched, spec_slots=spec_slots)


def test_speculation_beats_straggler():
    setup = _straggler_setup(spec_slots=2)
    pol_off = PolicyConfig(placement=PLACE_ROUND_ROBIN, speculation=SPEC_OFF)
    pol_on = PolicyConfig(placement=PLACE_ROUND_ROBIN, speculation=SPEC_ON)
    off = simulate(setup, pol_off)
    on = simulate(setup, pol_on)
    assert not bool(off.stalled) and not bool(on.stalled)
    # the clone on a healthy host finishes first and wins
    assert int(on.spec_launches) >= 1
    assert int(on.spec_wins) >= 1
    assert float(on.time) < float(off.time)
    # the losing original's runtime is accounted as waste
    assert float(on.spec_wasted) > 0.0
    # speculation=off on the SAME armed setup keeps every counter at zero
    assert int(off.spec_launches) == 0 and int(off.spec_wins) == 0
    assert float(off.spec_wasted) == 0.0
    consts, meta = make_consts(setup)
    for label, s in (("spec-on", on), ("spec-off", off)):
        check_all(consts, meta, s, label=label)


def test_speculation_policy_inert_without_slots():
    """``speculation=on`` with zero clone capacity is bitwise the off
    program — capacity is the structural switch, the policy only picks
    within it."""
    setup = _straggler_setup(spec_slots=0)
    off = simulate(setup, PolicyConfig(placement=PLACE_ROUND_ROBIN,
                                       speculation=SPEC_OFF))
    on = simulate(setup, PolicyConfig(placement=PLACE_ROUND_ROBIN,
                                      speculation=SPEC_ON))
    assert_states_equal(off, on, "no-slots")


def test_clone_never_slower_tie_goes_to_original():
    """On a healthy cluster with clone slots armed, speculation may fire
    (rate noise) but can never lose time: first-finish-wins with ties to
    the original keeps the on-makespan <= off-makespan."""
    topo = leaf_spine(2, 2, 2)
    setup = build_setup(uniform_workload(n_jobs=2, seed=0),
                        make_cluster(topo), spec_slots=2)
    off = simulate(setup, PolicyConfig(speculation=SPEC_OFF))
    on = simulate(setup, PolicyConfig(speculation=SPEC_ON))
    assert float(on.time) <= float(off.time) + 1e-3


# ---------------------------------------------------------------------------
# controller failover
# ---------------------------------------------------------------------------


def test_failover_parks_requests_and_counts(mini_setup):
    base_cfg = CtrlPlaneConfig(install_latency=0.05, ctrl_rate=500.0,
                               table_slots=8)
    fo_cfg = dataclasses.replace(base_cfg, ctrl_fail_t=0.0,
                                 ctrl_recover_t=1e9, failover_delay=5.0,
                                 backup_rate=50.0, backup_latency=0.5)
    base = simulate(with_ctrl(mini_setup, base_cfg),
                    PolicyConfig(job_concurrency=2))
    fo = simulate(with_ctrl(mini_setup, fo_cfg),
                  PolicyConfig(job_concurrency=2))
    assert not bool(fo.stalled)
    # the primary died before the first request: exactly one failover, the
    # whole run served by the slower backup after the handover gap
    assert int(fo.ctrl_failovers) == 1
    assert float(fo.ctrl_failover_park) > 0.0
    assert float(fo.time) > float(base.time)
    # a finite-primary run never touching the outage keeps counters at 0
    assert int(base.ctrl_failovers) == 0
    assert float(base.ctrl_failover_park) == 0.0
    consts, meta = make_consts(with_ctrl(mini_setup, fo_cfg))
    check_all(consts, meta, fo, label="failover")


def test_failover_validate_rejections():
    with pytest.raises(ValueError):
        CtrlPlaneConfig(ctrl_fail_t=10.0, ctrl_recover_t=5.0).validate()
    with pytest.raises(ValueError):
        CtrlPlaneConfig(ctrl_fail_t=10.0, failover_delay=-1.0).validate()
    with pytest.raises(ValueError):
        CtrlPlaneConfig(ctrl_fail_t=10.0, backup_rate=0.0).validate()


# ---------------------------------------------------------------------------
# check_finite falsifiability + chaos accounting
# ---------------------------------------------------------------------------


def test_check_finite_catches_doctored_nan(mini_setup):
    consts, meta = make_consts(mini_setup)
    s = simulate(mini_setup, PolicyConfig(job_concurrency=2))
    check_finite(consts, meta, s)                       # clean state passes
    arr = np.asarray(s.task_rem).copy()
    arr[0] = np.nan
    with pytest.raises(AssertionError, match="task_rem"):
        check_finite(consts, meta, s._replace(task_rem=arr))
    arr = np.asarray(s.host_energy).copy()
    arr[0] = np.inf
    with pytest.raises(AssertionError, match="host_energy"):
        check_finite(consts, meta, s._replace(host_energy=arr))
    # the documented sentinels stay allowed: NaN timestamps, inf park
    bad_ts = np.asarray(s.task_start).copy()
    bad_ts[0] = np.inf                                  # inf is NOT allowed
    with pytest.raises(AssertionError, match="task_start"):
        check_finite(consts, meta, s._replace(task_start=bad_ts))


def test_check_chaos_catches_doctored_counters(mini_setup):
    consts, meta = make_consts(mini_setup)
    s = simulate(mini_setup, PolicyConfig(job_concurrency=2))
    check_chaos(consts, meta, s)
    with pytest.raises(AssertionError, match="without clone slots"):
        check_chaos(consts, meta,
                    s._replace(spec_launches=np.int32(3)))
    with pytest.raises(AssertionError, match="degradation schedule"):
        check_chaos(consts, meta,
                    s._replace(degraded_time=np.float32(1.0)))
    with pytest.raises(AssertionError, match="ctrl plane off"):
        check_chaos(consts, meta,
                    s._replace(ctrl_failovers=np.int32(1)))


def test_chaos_rows_metrics():
    """``Results.rows`` carries the six §13 metrics and they are exactly
    zero on a chaos-free scenario."""
    from repro.api import Experiment
    res = Experiment("leaf-spine", policies=[
        ("sdn", PolicyConfig(routing=ROUTE_SDN, job_concurrency=2))]).run()
    row = res.rows()[0]
    for key in ("spec_launches", "spec_wins", "wasted_spec_work_s",
                "degraded_time_s", "failover_count", "failover_park_s"):
        assert key in row
        assert row[key] == 0


# ---------------------------------------------------------------------------
# composition: everything at once, batch and streaming
# ---------------------------------------------------------------------------


def test_chaos_scenarios_registered():
    for name in ("paper-fabric-chaos", "leaf-spine-chaos"):
        sc = get_scenario(name)
        setup = sc.build()
        assert setup.degradation is not None
        assert setup.degradation.any_degradation
        assert setup.spec_slots > 0
    assert get_scenario("paper-fabric-chaos").build().ctrl is not None
    # link gray windows are drawn per cable: both directed slots agree
    deg = get_scenario("paper-fabric-chaos").build().degradation
    assert np.array_equal(deg.link_slow_t[0::2], deg.link_slow_t[1::2],
                          equal_nan=True)


def test_chaos_composition_through_run_stream():
    """Outages x degradation x controller failover x speculation, streamed
    through the slot-recycling ring: conservation holds, the run drains,
    and the chaos counters surface in ``StreamResults.summary``."""
    from repro.api import Experiment
    from repro.scenarios.arrivals import ServiceClass, TraceArrivals
    from repro.scenarios.workloads import JobTemplate

    setup = get_scenario("leaf-spine", n_jobs=2).build()
    topo = setup.cluster.topo
    n_h, n_l = topo.n_hosts, topo.n_links
    deg = host_slowdown(n_h, n_l, host=0, at=0.0, factor=0.1)
    fail = host_crash(n_h, n_l, host=1, at=20.0, recover_at=60.0)
    ctrl = CtrlPlaneConfig(install_latency=0.02, ctrl_rate=1000.0,
                           table_slots=8, ctrl_fail_t=10.0,
                           ctrl_recover_t=1e9, failover_delay=1.0,
                           backup_rate=200.0, backup_latency=0.1)
    chaos_setup = dataclasses.replace(setup, degradation=deg, failures=fail,
                                      ctrl=ctrl, spec_slots=2)
    times = tuple(4.0 * i for i in range(8))
    arrivals = TraceArrivals(
        times=times,
        classes=(ServiceClass("only", slo_s=500.0,
                              template=JobTemplate(n_map=2, n_reduce=1)),))
    exp = Experiment(
        scenarios=("chaos-stream", chaos_setup),
        policies=[("spec-on", PolicyConfig(
            routing=ROUTE_SDN, placement=PLACE_ROUND_ROBIN,
            speculation=SPEC_ON, job_concurrency=2))])
    res = exp.run_stream(arrivals, horizon=30.0, slots=4, chunk_steps=64)
    assert res.stats.refills > 0         # the ring actually recycled
    check_stream(res, label="chaos-stream")
    summ = res.summary(0)
    assert summ["failover_count"] >= 1
    assert summ["degraded_time_s"] > 0.0
    assert summ["spec_launches"] >= summ["spec_wins"] >= 0
