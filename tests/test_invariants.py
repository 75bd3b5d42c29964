"""The registry-wide invariant harness (DESIGN.md §4/§10): every
registered scenario x a policy grid spanning every axis — including the
new install_mode / migration control-plane axes — runs as ONE packed grid
and every cell must satisfy every invariant in tests/invariants.py."""
import jax
import jax.numpy as jnp

from invariants import (ALL_INVARIANTS, check_all, check_slots, check_stream,
                        grid_check_all)
from repro.api import runners
from repro.core.policies import (INSTALL_PROACTIVE, MIG_CONGESTION,
                                 PLACE_ROUND_ROBIN, PolicyConfig,
                                 RECOVERY_RESUME, ROUTE_LEGACY, ROUTE_SDN,
                                 SPEC_ON, TRAFFIC_WATERFILL)
from repro.scenarios import get_scenario, list_scenarios
from repro.scenarios.sweep import pack_setups, policy_arrays

# every registered scenario at CPU-test size (structures intact: topology
# family, workload shape, failure trace, ctrl config)
SCENARIOS = [
    ("paper-fabric", dict(split=1)),
    ("fat-tree", dict(n_jobs=4)),
    ("al-fares-fat-tree", dict(n_each=1, split=1, k_max=4)),
    ("leaf-spine", dict(n_jobs=4)),
    ("canonical-tree", dict(n_jobs=4)),
    ("leaf-spine-xl", dict(n_spine=2, n_leaf=2, hosts_per_leaf=2, n_jobs=4,
                           max_scale=1.5)),
    ("paper-fabric-failures", dict(split=1)),
    ("leaf-spine-failures", dict(n_jobs=4)),
    ("paper-fabric-ctrl", dict(split=1)),
    ("leaf-spine-ctrl", dict(n_jobs=4)),
    ("leaf-spine-stream", dict(horizon=160.0, max_jobs=4)),
    ("paper-fabric-chaos", dict(split=1)),
    ("leaf-spine-chaos", dict(n_jobs=4)),
]

# one policy per branch family, cycling the secondary axes — including
# both §10 axes, so ctrl scenarios exercise proactive install and
# congestion migration inside the same packed grid
POLICIES = [
    ("sdn", PolicyConfig(routing=ROUTE_SDN, job_concurrency=2)),
    ("legacy", PolicyConfig(routing=ROUTE_LEGACY, job_concurrency=2,
                            placement=PLACE_ROUND_ROBIN)),
    ("sdn-pro", PolicyConfig(routing=ROUTE_SDN,
                             install_mode=INSTALL_PROACTIVE,
                             traffic=TRAFFIC_WATERFILL, seed=1)),
    ("sdn-mig", PolicyConfig(routing=ROUTE_SDN, migration=MIG_CONGESTION,
                             recovery=RECOVERY_RESUME, job_concurrency=2)),
    ("sdn-spec", PolicyConfig(routing=ROUTE_SDN, speculation=SPEC_ON,
                              placement=PLACE_ROUND_ROBIN,
                              job_concurrency=2, seed=2)),
]


def test_scenario_list_covers_registry():
    """This harness must grow with the registry — a newly registered
    scenario that is not invariant-checked fails here."""
    covered = {name for name, _ in SCENARIOS}
    assert covered == set(list_scenarios())


def test_policy_grid_covers_ctrl_axes():
    pols = [p for _, p in POLICIES]
    assert any(p.install_mode == INSTALL_PROACTIVE for p in pols)
    assert any(p.migration == MIG_CONGESTION for p in pols)
    assert any(p.routing == ROUTE_LEGACY for p in pols)


def test_registry_policy_grid_invariants():
    """The whole registry x policy grid in one vmapped program; every
    final state passes every invariant."""
    setups = [get_scenario(name, **kw).build() for name, kw in SCENARIOS]
    consts, meta = pack_setups(setups)
    assert meta.has_ctrl and meta.has_failures   # both subsystems traced in
    pols = {k: jnp.asarray(v) for k, v in
            policy_arrays([p for _, p in POLICIES]).items()}
    states = jax.block_until_ready(
        runners.get_runner(meta, "grid")(consts, pols))
    grid_check_all(consts, meta, states,
                   [name for name, _ in SCENARIOS],
                   [name for name, _ in POLICIES])


def test_invariants_catch_violations():
    """The harness itself must be falsifiable: a doctored final state
    trips the matching checker."""
    import numpy as np
    import pytest
    setup = get_scenario("leaf-spine", n_jobs=2).build()
    from repro.core.engine import make_consts
    from repro.core import simulate
    c, meta = make_consts(setup)
    s = simulate(setup, PolicyConfig(job_concurrency=2))
    check_all(c, meta, s, label="healthy")
    assert len(ALL_INVARIANTS) >= 5
    bad = s._replace(vm_load=np.asarray(s.vm_load) + 1)
    with pytest.raises(AssertionError, match="vm_load"):
        check_all(c, meta, bad, label="doctored")
    bad2 = s._replace(ctrl_installs=np.int32(3))
    with pytest.raises(AssertionError):
        check_all(c, meta, bad2, label="doctored-ctrl")
    # slot conservation must be falsifiable too: resurrect one DONE task
    # without a matching vm_load entry
    ts = np.asarray(s.task_state).copy()
    ts[np.flatnonzero(ts == 2)[0]] = 1   # DONE -> ACTIVE
    with pytest.raises(AssertionError, match="census"):
        check_slots(c, meta, s._replace(task_state=ts), label="doctored")


def test_streaming_registry_invariants():
    """Drive the streaming engine over registry scenarios and check the
    streaming ledger (check_stream) plus every per-state invariant —
    including slot conservation — on the drained final states against the
    consts of each lane's LAST ring generation."""
    from repro.api import Experiment
    from repro.scenarios.registry import stream_arrivals

    for scen, arrivals, horizon in [
            ("leaf-spine", stream_arrivals(rate=0.08, seed=2), 120.0),
            ("canonical-tree", stream_arrivals(rate=0.06, seed=3), 150.0)]:
        exp = Experiment(scenarios=get_scenario(scen, n_jobs=2),
                         policies=POLICIES[:2])
        res = exp.run_stream(arrivals, horizon, slots=3, chunk_steps=48,
                             return_states=True)
        assert res.stats.refills > 0     # the ring actually recycled
        check_stream(res, label=scen)
        for pi in range(res.n_policies):
            check_all(res.final_consts[pi], res.meta, res.final_states[pi],
                      label=f"{scen}/{res.policy_names[pi]}")
