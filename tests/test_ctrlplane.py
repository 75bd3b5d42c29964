"""Control-plane resource model (DESIGN.md §10): off-switch identity,
install-latency breakpoints, controller queueing, LRU flow tables,
proactive install overlap, and migrate-on-congestion."""
import numpy as np
import pytest

from simhelpers import with_ctrl, with_failures, dims, assert_states_equal
from invariants import check_all
from repro.core import (CtrlPlaneConfig, INSTALL_PROACTIVE, MIG_CONGESTION,
                        PolicyConfig, ROUTE_LEGACY, ROUTE_SDN, host_crash,
                        no_ctrl, simulate)
from repro.core.engine import make_consts
from repro.core.flows import Flow, flows_setup
from repro.core.topology import leaf_spine

CTRL = CtrlPlaneConfig(install_latency=0.05, ctrl_rate=500.0, table_slots=8)


@pytest.fixture(scope="module")
def ls_flow_setup():
    """One 8-second flow crossing 3 switches (leaf, spine, leaf)."""
    return flows_setup(leaf_spine(2, 2, 2), [Flow(0, 2, 8.0)])


def test_config_validation_and_any_ctrl():
    assert not no_ctrl().any_ctrl
    assert not CtrlPlaneConfig().any_ctrl
    for cfg in (CtrlPlaneConfig(install_latency=0.1),
                CtrlPlaneConfig(ctrl_rate=100.0),
                CtrlPlaneConfig(table_slots=4),
                CtrlPlaneConfig(mig_threshold=8.0)):
        assert cfg.any_ctrl
    with pytest.raises(ValueError):
        CtrlPlaneConfig(install_latency=-1.0).validate()
    with pytest.raises(ValueError):
        CtrlPlaneConfig(ctrl_rate=0.0).validate()
    with pytest.raises(ValueError):
        CtrlPlaneConfig(table_slots=-1).validate()


def test_identity_config_is_the_off_switch(ls_flow_setup):
    """ctrl=no_ctrl() and ctrl=None build the same meta (has_ctrl=False)
    and the same bitwise run — the off switch is trace-time."""
    _, meta_none = make_consts(ls_flow_setup)
    _, meta_id = make_consts(with_ctrl(ls_flow_setup, no_ctrl()))
    assert not meta_none.has_ctrl and not meta_id.has_ctrl
    assert meta_none == meta_id
    a = simulate(ls_flow_setup, PolicyConfig())
    b = simulate(with_ctrl(ls_flow_setup, no_ctrl()), PolicyConfig())
    assert_states_equal(a, b, "no_ctrl off switch")


def test_install_latency_delays_exactly(ls_flow_setup):
    """Reactive install with an unconstrained controller: one flow pays
    exactly one install latency before transmitting."""
    base = simulate(ls_flow_setup, PolicyConfig())
    assert float(base.time) == pytest.approx(8.0, rel=1e-4)
    for lat in (0.25, 1.5):
        s = simulate(with_ctrl(ls_flow_setup,
                               CtrlPlaneConfig(install_latency=lat)),
                     PolicyConfig())
        assert not bool(s.stalled)
        assert float(s.time) == pytest.approx(8.0 + lat, rel=1e-4)
        assert float(np.asarray(s.pkt_install_wait).sum()) == pytest.approx(
            lat, rel=1e-4)


def test_legacy_routing_bypasses_controller(ls_flow_setup):
    """Legacy forwarding needs no flow-mod round trip: zero installs, and
    the makespan matches the ctrl-free legacy run exactly."""
    cfg = CtrlPlaneConfig(install_latency=0.5, ctrl_rate=50.0, table_slots=2)
    base = simulate(ls_flow_setup, PolicyConfig(routing=ROUTE_LEGACY))
    s = simulate(with_ctrl(ls_flow_setup, cfg),
                 PolicyConfig(routing=ROUTE_LEGACY))
    assert int(s.ctrl_installs) == 0
    assert float(s.time) == float(base.time)
    assert float(np.asarray(s.pkt_install_wait).sum()) == 0.0


def test_rate_limited_controller_serializes_installs():
    """A finite-rate controller is a FIFO queue: concurrent flow setups
    wait on each other and the queue wait is accounted."""
    setup = flows_setup(leaf_spine(2, 2, 2),
                        [Flow(0, 2, 8.0), Flow(1, 3, 8.0)])
    fast = simulate(with_ctrl(setup, CtrlPlaneConfig(install_latency=0.01)),
                    PolicyConfig())
    slow = simulate(with_ctrl(setup, CtrlPlaneConfig(install_latency=0.01,
                                                     ctrl_rate=2.0)),
                    PolicyConfig())
    assert not bool(slow.stalled)
    assert float(slow.ctrl_queue_wait) > 0.0
    assert float(fast.ctrl_queue_wait) == 0.0
    assert float(slow.time) > float(fast.time)


def test_lru_table_evicts_and_conserves():
    """With one slot per switch, a second flow through the same spine
    displaces the first flow's rule — and the conservation identity
    ``occupied == installs - evictions`` holds exactly."""
    setup = flows_setup(leaf_spine(1, 2, 2),
                        [Flow(0, 2, 4.0), Flow(1, 3, 4.0)])
    s = simulate(with_ctrl(setup, CtrlPlaneConfig(install_latency=0.01,
                                                  table_slots=1)),
                 PolicyConfig())
    assert not bool(s.stalled)
    assert int(s.ctrl_evictions) >= 1
    occupied = int((np.asarray(s.ftab_pair) >= 0).sum())
    assert occupied == int(s.ctrl_installs) - int(s.ctrl_evictions)


def test_tableless_conservation():
    """table_slots=0 models install latency with no caching: every install
    is immediately 'evicted' and the identity still balances."""
    setup = flows_setup(leaf_spine(2, 2, 2), [Flow(0, 2, 8.0)])
    s = simulate(with_ctrl(setup, CtrlPlaneConfig(install_latency=0.1)),
                 PolicyConfig())
    assert int(s.ctrl_installs) > 0
    assert int(s.ctrl_installs) == int(s.ctrl_evictions)
    assert np.asarray(s.ftab_pair).size == 0


def test_proactive_overlaps_install_latency(mini_setup):
    """Proactive install pre-pins routes at admission, overlapping the
    install round trip with job queueing: on the paper fabric it recovers
    (nearly all of) the reactive makespan penalty."""
    setup = with_ctrl(mini_setup, CTRL)
    react = simulate(setup, PolicyConfig(job_concurrency=2))
    pro = simulate(setup, PolicyConfig(job_concurrency=2,
                                       install_mode=INSTALL_PROACTIVE))
    assert not bool(react.stalled) and not bool(pro.stalled)
    assert float(pro.time) < float(react.time)
    # churn-evicted pins fall back to reactive install and are counted
    assert int(pro.ctrl_reinstalls) >= 0
    c, meta = make_consts(setup)
    check_all(c, meta, pro, label="paper-fabric/proactive")
    check_all(c, meta, react, label="paper-fabric/reactive")


def test_legacy_beats_sdn_under_priced_controller(mini_setup):
    """The headline regime (the acceptance bar for DESIGN.md §10): with
    the controller priced in, legacy's zero-install static hash finishes
    the paper-fabric mix FASTER than reactive SDN — the comparison the
    instant-oracle model could never produce."""
    setup = with_ctrl(mini_setup, CTRL)
    sdn = simulate(setup, PolicyConfig(routing=ROUTE_SDN, job_concurrency=2))
    legacy = simulate(setup, PolicyConfig(routing=ROUTE_LEGACY,
                                          job_concurrency=2))
    assert not bool(sdn.stalled) and not bool(legacy.stalled)
    assert float(legacy.time) < float(sdn.time)
    # and WITHOUT the controller priced, SDN wins the same comparison
    sdn0 = simulate(mini_setup, PolicyConfig(routing=ROUTE_SDN,
                                             job_concurrency=2))
    legacy0 = simulate(mini_setup, PolicyConfig(routing=ROUTE_LEGACY,
                                                job_concurrency=2))
    assert float(sdn0.time) < float(legacy0.time)


def test_migration_rehomes_and_completes():
    """Migrate-on-congestion (S-CORE): with a finite threshold the
    controller re-homes hot VMs — runs migrate, packets re-route, the
    workload still completes; under migration=static nothing moves."""
    from repro.scenarios import get_scenario
    setup = get_scenario("leaf-spine-ctrl").build()
    mig = simulate(setup, PolicyConfig(routing=ROUTE_SDN,
                                       migration=MIG_CONGESTION))
    static = simulate(setup, PolicyConfig(routing=ROUTE_SDN))
    assert not bool(mig.stalled) and not bool(static.stalled)
    assert int(np.asarray(mig.vm_migrations).sum()) > 0
    assert int(np.asarray(static.vm_migrations).sum()) == 0
    c, meta = make_consts(setup)
    assert not np.array_equal(np.asarray(mig.vm_host), np.asarray(c.vm_host))
    assert np.array_equal(np.asarray(static.vm_host), np.asarray(c.vm_host))
    check_all(c, meta, mig, label="leaf-spine-ctrl/mig")


def test_ctrl_composes_with_failures(mini_setup):
    """§7 x §10: a host crash under a priced controller still recovers,
    and both subsystems' invariants hold on the same run."""
    sched = host_crash(*dims(mini_setup), host=0, at=30.0, recover_at=300.0)
    setup = with_ctrl(with_failures(mini_setup, sched), CTRL)
    s = simulate(setup, PolicyConfig(job_concurrency=2))
    assert not bool(s.stalled)
    assert int(np.asarray(s.task_restarts).sum()) >= 1
    c, meta = make_consts(setup)
    assert meta.has_ctrl and meta.has_failures
    check_all(c, meta, s, label="paper-fabric/failures+ctrl")


def test_ctrl_metrics_reported(mini_setup):
    """rows() carries the §10 columns, zeroed without a ctrl config."""
    from repro.api import Experiment
    res = Experiment(
        scenarios=[("plain", mini_setup), ("priced", with_ctrl(mini_setup,
                                                               CTRL))],
        policies=[("sdn", PolicyConfig(routing=ROUTE_SDN,
                                       job_concurrency=2))]).run()
    rows = {r["scenario"]: r for r in res.rows()}
    keys = {"install_wait_s", "rule_installs", "rule_evictions",
            "rule_reinstalls", "ctrl_queue_wait_s", "vm_migrations"}
    assert keys <= set(rows["plain"])
    assert rows["plain"]["rule_installs"] == 0
    assert rows["plain"]["install_wait_s"] == 0.0
    assert rows["priced"]["rule_installs"] > 0
    assert rows["priced"]["install_wait_s"] > 0.0
    # the packed no-ctrl replica never moves a VM
    import jax
    c0 = jax.tree_util.tree_map(lambda a: a[0], res.consts)
    s0 = res.state(0, 0)
    assert np.array_equal(np.asarray(s0.vm_host)[:int(c0.n_vms)],
                          np.asarray(c0.vm_host)[:int(c0.n_vms)])
