"""Unified Experiment API (DESIGN.md §6): policy-field registry single
source of truth, compiled-runner cache (no retrace on equal SimMeta), and
bit-identical deprecated shims."""
import dataclasses
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from simhelpers import assert_states_equal as assert_states_identical
from simhelpers import tiny_setups as _tiny_setups
from repro.api import (Experiment, PolicyConfig, SimMeta, as_policy_arrays,
                       policy_field_names, runners)
from repro.core import (PLACE_RANDOM, ROUTE_LEGACY, ROUTE_SDN, paper_setup,
                        simulate, simulate_batch, simulate_scenarios)
from repro.core import policies as policy_mod
from repro.core.engine import make_consts
from repro.scenarios import pack_setups, policy_arrays, sweep_grid


# ---------------------------------------------------------------------------
# policy-field registry: ONE source of truth
# ---------------------------------------------------------------------------


def test_registry_matches_engine_consumed_keys():
    """The keys the engine actually reads (pol["..."]) must be exactly the
    registered policy fields — no hand-duplicated lists anywhere."""
    src = (Path(policy_mod.__file__).parent / "engine.py").read_text()
    consumed = set(re.findall(r'pol\[["\'](\w+)["\']\]', src))
    assert consumed == set(policy_field_names())


def test_policy_config_and_packers_derive_from_registry():
    names = policy_field_names()
    assert tuple(vars(PolicyConfig())) == names
    assert tuple(PolicyConfig().as_arrays()) == names
    assert tuple(as_policy_arrays(None)) == names
    assert tuple(policy_arrays([PolicyConfig()])) == names


def test_as_policy_arrays_fills_defaults_and_rejects_unknown():
    pol = as_policy_arrays({"routing": ROUTE_LEGACY})
    assert int(pol["routing"]) == ROUTE_LEGACY
    assert int(pol["job_concurrency"]) == 1_000_000
    assert pol["seed"].dtype == jnp.int32
    with pytest.raises(KeyError):
        as_policy_arrays({"no_such_axis": 1})


def test_register_policy_field_extends_config():
    """Adding a policy axis = one registration; PolicyConfig (the SAME
    import-time class), as_arrays and the sweep packers all pick it up with
    no further edits, and pre-existing instances stay usable."""
    old_instance = PolicyConfig(job_concurrency=3)
    try:
        policy_mod.register_policy_field("test_knob", 7, doc="test-only")
        cfg = PolicyConfig(test_knob=8)       # import-time binding, not stale
        assert cfg.test_knob == 8
        assert int(cfg.as_arrays()["test_knob"]) == 8
        assert int(PolicyConfig().as_arrays()["test_knob"]) == 7
        assert int(as_policy_arrays({"test_knob": 9})["test_knob"]) == 9
        assert "test_knob" in policy_arrays([PolicyConfig()])
        # instances born before the registration fall back to the default
        assert int(old_instance.as_arrays()["test_knob"]) == 7
        assert old_instance.replace(seed=1).seed == 1
        with pytest.raises(ValueError):
            policy_mod.register_policy_field("test_knob", 0)
        with pytest.raises(TypeError):
            PolicyConfig(not_an_axis=1)
    finally:
        policy_mod._REGISTRY.pop("test_knob", None)


# ---------------------------------------------------------------------------
# compiled-runner cache: second run with equal SimMeta never retraces
# ---------------------------------------------------------------------------


def test_cache_no_retrace_on_identical_meta():
    runners.cache_clear()
    scens = _tiny_setups()
    pol = PolicyConfig(placement=PLACE_RANDOM)

    r1 = Experiment(scenarios=scens[0], policies=pol).run()
    traces_after_first = runners.trace_count()
    assert traces_after_first == 1

    r2 = Experiment(scenarios=scens[0], policies=pol).run()
    assert runners.trace_count() == traces_after_first, \
        "second run with identical SimMeta must not retrace"
    assert_states_identical(r1.states, r2.states)

    # a different scenario => different SimMeta => a fresh trace
    Experiment(scenarios=scens[1], policies=pol).run()
    assert runners.trace_count() == traces_after_first + 1

    # and back to the first meta: still cached
    Experiment(scenarios=scens[0], policies=pol).run()
    assert runners.trace_count() == traces_after_first + 1


def test_fleet_no_retrace_on_identical_meta():
    """Experiment.run_fleet: the chunk programs (which bump the same
    trace counter at trace time) compile exactly once — a second
    same-meta, same-width invocation is trace-free."""
    runners.cache_clear()
    setup = _tiny_setups()[0]
    pols = [PolicyConfig(seed=i) for i in range(3)]

    exp = Experiment(scenarios=setup, policies=pols)
    r1 = exp.run_fleet(width=2, chunk_steps=8)
    n = runners.trace_count()
    assert n >= 1

    r2 = Experiment(scenarios=setup, policies=pols).run_fleet(
        width=2, chunk_steps=8)
    assert runners.trace_count() == n, \
        "second run_fleet with identical SimMeta must not retrace"
    assert_states_identical(r1.states, r2.states)


def test_stream_no_retrace_on_identical_meta():
    """Experiment.run_stream: chunk/refill programs compile exactly once —
    replaying the same arrival trace through an equal-meta ring is
    trace-free the second time."""
    from repro.scenarios import get_scenario
    from repro.scenarios.arrivals import PoissonArrivals

    runners.cache_clear()
    setup = get_scenario("leaf-spine", n_jobs=2).build()
    arrivals = PoissonArrivals(rate=0.05, seed=0)

    def one_run():
        exp = Experiment(scenarios=("leaf-spine", setup),
                         policies=PolicyConfig(job_concurrency=2))
        return exp.run_stream(arrivals, horizon=120.0, slots=4,
                              chunk_steps=32)

    r1 = one_run()
    n = runners.trace_count()
    assert n >= 1

    r2 = one_run()
    assert runners.trace_count() == n, \
        "second run_stream with identical SimMeta must not retrace"
    assert r1.jobs[0]["seq"].size == r2.jobs[0]["seq"].size


def test_cache_shared_by_shims():
    """simulate() reuses the same cache — repeated calls are trace-free."""
    runners.cache_clear()
    setup = _tiny_setups()[0][1]
    simulate(setup, PolicyConfig())
    n = runners.trace_count()
    simulate(setup, PolicyConfig())
    simulate(setup, {"routing": ROUTE_SDN})
    assert runners.trace_count() == n


def test_simmeta_hashable_and_dict_compatible():
    _, meta = make_consts(_tiny_setups()[0][1])
    assert isinstance(meta, SimMeta)
    assert hash(meta) == hash(SimMeta.coerce(meta))
    assert meta["n_vms"] == meta.n_vms          # legacy spelling
    with pytest.raises(KeyError):
        meta["not_a_field"]
    legacy = {f.name: getattr(meta, f.name)
              for f in dataclasses.fields(SimMeta)}
    assert SimMeta.coerce(legacy) == meta


# ---------------------------------------------------------------------------
# shim equivalence: old entry points == Experiment path, bit for bit
# ---------------------------------------------------------------------------


def test_simulate_shim_bit_identical_on_paper_fabric():
    setup = paper_setup(seed=0)
    pol = PolicyConfig(routing=ROUTE_SDN, job_concurrency=2)
    old = simulate(setup, pol)
    new = Experiment(scenarios=setup, policies=pol).run()
    assert_states_identical(old, new.state(), "simulate vs Experiment: ")


def test_simulate_batch_shim_bit_identical():
    setup = _tiny_setups()[0][1]
    pols = [PolicyConfig(routing=ROUTE_SDN, job_concurrency=2),
            PolicyConfig(routing=ROUTE_LEGACY, job_concurrency=2)]
    old = simulate_batch(setup, policy_arrays(pols))
    new = Experiment(scenarios=setup,
                     policies=[("sdn", pols[0]), ("legacy", pols[1])]).run()
    squeezed = jax.tree_util.tree_map(lambda a: a[0], new.states)
    assert_states_identical(old, squeezed, "simulate_batch vs Experiment: ")


def test_packed_two_scenario_batch_bit_identical():
    """sweep_grid (deprecated) vs Experiment on a packed heterogeneous
    two-scenario batch, plus the zipped simulate_scenarios diagonal."""
    scens = _tiny_setups()
    pols = [("a", PolicyConfig(job_concurrency=2)),
            ("b", PolicyConfig(placement=PLACE_RANDOM, job_concurrency=2))]
    res = Experiment(scenarios=scens, policies=pols).run()
    grid = sweep_grid(scens, pols)
    S, P = res.n_scenarios, res.n_policies
    regrid = jax.tree_util.tree_map(
        lambda a: a.reshape((S, P) + a.shape[1:]), grid.states)
    assert_states_identical(regrid, res.states, "sweep_grid vs Experiment: ")

    consts, meta = pack_setups([s for _, s in scens])
    zipped = simulate_scenarios(
        consts, meta,
        {k: jnp.asarray(v) for k, v in policy_arrays(
            [p for _, p in pols]).items()})
    diag = jax.tree_util.tree_map(
        lambda a: np.stack([np.asarray(a)[0, 0], np.asarray(a)[1, 1]]),
        res.states)
    assert_states_identical(zipped, diag, "simulate_scenarios vs diagonal: ")


# ---------------------------------------------------------------------------
# Experiment/Results surface
# ---------------------------------------------------------------------------


def test_experiment_seeds_cross_product():
    e = Experiment(scenarios=_tiny_setups()[0],
                   policies=[("p", PolicyConfig())], seeds=[0, 1, 2])
    assert e.policy_names == ["p/s0", "p/s1", "p/s2"]
    assert [p.seed for _, p in e.policies] == [0, 1, 2]
    with pytest.raises(ValueError):
        Experiment(scenarios=_tiny_setups()[0], seeds=[])


def test_experiment_accepts_named_registry_name_pairs():
    e = Experiment(scenarios=[("mine", "canonical-tree")])
    assert e.scenario_names == ["mine"]
    # a top-level (str, str) tuple reads as a sequence of two names
    e2 = Experiment(scenarios=("fat-tree", "canonical-tree"))
    assert len(e2.scenarios) == 2


def test_sweep_grid_shim_preserves_duplicate_labels():
    (name, setup), _ = _tiny_setups()
    res = sweep_grid([("x", setup), ("x", setup)],
                     [("p", PolicyConfig(job_concurrency=2))])
    assert res.scenario_names == ["x", "x"]
    assert res.policy_names == ["p", "p"]


def test_runner_cache_is_lru_bounded():
    runners.cache_clear()
    _, meta = make_consts(_tiny_setups()[0][1])
    for i in range(runners.CACHE_MAX + 5):
        runners.get_runner(meta.replace(max_steps=meta.max_steps + i),
                           "single")
    assert runners.cache_size() == runners.CACHE_MAX
    runners.cache_clear()


def test_results_masks_pad_jobs():
    """In a packed batch the smaller scenario's pad jobs must read NaN,
    and the valid-job numbers must match the scenario's own single run."""
    scens = _tiny_setups()     # 2 jobs vs 3 jobs -> one pad job slot
    res = Experiment(scenarios=scens, policies=PolicyConfig()).run()
    jr = res.job_report()
    assert jr["completion_measured"].shape == (2, 1, 3)
    assert np.all(np.isnan(jr["completion_measured"][0, 0, 2:]))
    assert np.all(np.isfinite(jr["completion_measured"][0, 0, :2]))

    single = Experiment(scenarios=scens[0], policies=PolicyConfig()).run()
    np.testing.assert_allclose(
        np.asarray(single.job_report()["completion_measured"])[0, 0],
        jr["completion_measured"][0, 0, :2], rtol=1e-5)

    rows = res.rows()
    assert len(rows) == 2
    assert {r["scenario"] for r in rows} == {"leaf-spine", "canon-tree"}
    for r in rows:
        assert np.isfinite(r["mean_completion_s"]) and not r["stalled"]


def test_results_summary_matches_summarize():
    from repro.core import summarize
    setup = _tiny_setups()[0][1]
    pol = PolicyConfig(job_concurrency=2)
    res = Experiment(scenarios=setup, policies=pol).run()
    legacy = summarize(setup, simulate(setup, pol))
    mine = res.summary()
    for key in ("transmission_time", "completion_measured", "makespan_s",
                "total_energy_j", "stalled", "steps"):
        np.testing.assert_allclose(np.asarray(mine[key]),
                                   np.asarray(legacy[key]), rtol=1e-6)


def test_experiment_accepts_registry_names():
    res = Experiment(scenarios="canonical-tree",
                     policies={"job_concurrency": 2}).run()
    assert res.scenario_names == ["canonical-tree-d3f2"]
    assert not res.rows()[0]["stalled"]
