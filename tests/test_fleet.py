"""Fleet execution engine (DESIGN.md §9): bit-identity to the serial
runners across the scenario registry, cohort retire/refill bookkeeping,
and the keyed consts cache."""
import jax
import numpy as np
import pytest

from repro.api import (CohortSchedule, Experiment, StepPredictor,
                       consts_build_count, consts_cache_clear, run_fleet,
                       runners)
from repro.scenarios import list_scenarios

# leaf-spine-xl runs for minutes serially; its fleet path is covered by the
# slow-marked test below and by benchmarks/engine_profile.py's large tier.
REGISTRY = [n for n in list_scenarios() if "xl" not in n]

# routing × placement coverage: both routings, all three placements, with
# one pair per static signature so cohort grouping is exercised too
POLICIES = [
    {"routing": 0, "placement": 0},
    {"routing": 0, "placement": 2},
    {"routing": 1, "placement": 0},
    {"routing": 1, "placement": 1},
]
SEEDS = (0, 1, 2)


def assert_results_identical(a, b, context=""):
    """Leaf-by-leaf bit equality (NaN == NaN) between two Results grids."""
    for name, la, lb in zip(a.states._fields, a.states, b.states):
        la, lb = np.asarray(la), np.asarray(lb)
        assert la.shape == lb.shape, \
            f"{context}{name}: shape {la.shape} != {lb.shape}"
        assert np.array_equal(la, lb, equal_nan=True), \
            f"{context}{name}: values differ"


# ---------------------------------------------------------------------------
# bit-identity to the serial runner
# ---------------------------------------------------------------------------


def test_fleet_identical_across_registry():
    """One packed grid over every (non-xl) registry scenario — including
    the *-failures ones, whose schedules flow through the packed consts —
    times routing/placement times 3 seeds, drained by the fleet with a
    width small enough to force retire/refill cycles."""
    exp = Experiment(scenarios=REGISTRY, policies=POLICIES, seeds=SEEDS)
    serial = exp.run()
    fleet, stats = exp.run_fleet(width=5, chunk_steps=16, return_stats=True)
    assert_results_identical(serial, fleet, "registry grid: ")
    assert stats.sims == len(REGISTRY) * len(POLICIES) * len(SEEDS)
    # width 5 over 3-member cohorts: every cohort fits one wave
    assert stats.cohorts == len(REGISTRY) * len(POLICIES)


def test_fleet_identical_single_scenario_with_refill():
    """S == 1 path (unpacked consts) with width << members so lanes retire
    and refill mid-cohort."""
    exp = Experiment(scenarios="paper-fabric", policies=POLICIES[:1],
                     seeds=range(9))
    serial = exp.run()
    fleet, stats = exp.run_fleet(width=2, chunk_steps=8, return_stats=True)
    assert_results_identical(serial, fleet, "single-scenario: ")
    assert stats.refills > 0


def test_fleet_identical_length_divergent_bucket():
    """A deliberately length-divergent cohort: job_concurrency 1 serializes
    the whole workload (many more events) but is NOT a static field, so the
    short and long sims share one cohort and the early-exit/refill path has
    to cope with the spread."""
    pols = [{"job_concurrency": c, "seed": s}
            for c in (1, 1_000_000) for s in SEEDS]
    exp = Experiment(scenarios="leaf-spine", policies=pols)
    serial = exp.run()
    steps = np.asarray(serial.states.steps)[0]
    assert steps.max() >= steps.min() + 16, "bucket not length-divergent"
    fleet = exp.run_fleet(width=4, chunk_steps=8)
    assert_results_identical(serial, fleet, "divergent bucket: ")


def test_fleet_sharded_matches_serial():
    """The shard_map path: with >1 visible device (the CI job forces 8 via
    XLA_FLAGS) the lane axis is split over the fleet mesh; on one device
    this degrades to the plain jitted chunk.  Either way: bit-identical."""
    n_dev = jax.local_device_count()
    exp = Experiment(scenarios="paper-fabric", policies=POLICIES, seeds=SEEDS)
    serial = exp.run()
    fleet, stats = exp.run_fleet(width=8, chunk_steps=16, devices=n_dev,
                                 return_stats=True)
    assert_results_identical(serial, fleet, f"sharded x{n_dev}: ")
    assert stats.devices == n_dev


@pytest.mark.parametrize("name,overrides", [
    ("paper-fabric", {}),
    ("al-fares-fat-tree", {"k": 4, "n_each": 1, "k_max": 4}),
])
def test_fleet_identical_through_the_eq3_kernel(name, overrides):
    """The TPU's Eq. 3 lookup (the Pallas kernel, here through its
    interpreter) against the gather every other backend runs: the same
    campaign, SDN and legacy cohorts, ends in bit-identical states, and
    ``lookup_counts`` counts the path each program was lowered with."""
    from repro.core import fairshare
    from repro.scenarios import get_scenario
    exp = Experiment(scenarios=get_scenario(name, **overrides).build(),
                     policies=POLICIES[::2], seeds=(0, 1))
    got = {}
    try:
        for path, interpret in (("gather", False), ("kernel", True)):
            fairshare._INTERPRET = interpret
            runners.cache_clear()
            before = fairshare.lookup_counts()
            got[path] = exp.run_fleet(width=2, chunk_steps=16)
            after = fairshare.lookup_counts()
            assert after[path] > before[path]
            assert sum(after.values()) - sum(before.values()) \
                == after[path] - before[path]
    finally:
        fairshare._INTERPRET = False
        runners.cache_clear()
    assert_results_identical(got["gather"], got["kernel"], f"{name}: ")
    assert not np.asarray(got["gather"].states.stalled).any()


@pytest.mark.slow
def test_fleet_identical_xl():
    """leaf-spine-xl (the 128-host tier) through the fleet batch path."""
    exp = Experiment(scenarios="leaf-spine-xl", policies=POLICIES[2:])
    assert_results_identical(exp.run(), exp.run_fleet(width=2, chunk_steps=64),
                             "xl: ")


# ---------------------------------------------------------------------------
# cohort bookkeeping
# ---------------------------------------------------------------------------


def test_cohort_schedule_retire_refill_and_pads():
    sched = CohortSchedule(["a", "b", "c", "d", "e"], width=3)
    assert sched.lane == ["a", "b", "c"]
    assert not sched.pad_mask().any()
    assert sched.active

    # lane 1 finishes: retired, refilled from the queue
    retire, refill = sched.step(np.array([False, True, False]))
    assert retire == [(1, "b")]
    assert refill.tolist() == [False, True, False]
    assert sched.lane == ["a", "d", "c"]

    # everything finishes: e takes a lane, the other two become pads
    retire, refill = sched.step(np.array([True, True, True]))
    assert sorted(m for _, m in retire) == ["a", "c", "d"]
    assert refill.sum() == 1 and sched.lane.count(None) == 2
    assert sched.pad_mask().sum() == 2
    assert sched.active

    # pad lanes stay done and must NOT retire again
    retire, refill = sched.step(np.array([True, True, True]))
    assert [m for _, m in retire] == ["e"] and not refill.any()
    assert not sched.active
    assert sorted(m for _, m in sched.retired) == list("abcde")


def test_cohort_schedule_width_wider_than_members():
    sched = CohortSchedule(["a"], width=4)
    assert sched.pad_mask().tolist() == [False, True, True, True]
    retire, refill = sched.step(np.array([True] * 4))
    assert retire == [(0, "a")] and not refill.any()
    assert not sched.active


def test_step_predictor_orders_by_observation():
    pred = StepPredictor()
    # unobserved: the group estimate (or size prior) ties everything
    assert pred.predict("m1", "g", 10, 20) == pred.predict("m2", "g", 10, 20)
    pred.observe("m1", 100.0)
    pred.observe("m2", 10.0)
    assert pred.predict("m2", "g", 10, 20) < pred.predict("m1", "g", 10, 20)
    # EWMA moves toward new observations without forgetting everything
    pred.observe("m2", 100.0)
    assert 10.0 < pred.predict("m2", "g", 10, 20) < 100.0


def test_fleet_bucket_order_does_not_change_results():
    """Predictor-driven admission order is a pure scheduling choice: a
    calibrated predictor (second fleet) must reproduce the cold-start
    results bit-for-bit."""
    exp = Experiment(scenarios="paper-fabric", policies=POLICIES[:1],
                     seeds=range(6))
    pred = StepPredictor()
    first = run_fleet(exp, width=2, chunk_steps=8, predictor=pred)
    second = run_fleet(exp, width=2, chunk_steps=8, predictor=pred)
    assert_results_identical(first, second, "calibrated reorder: ")


# ---------------------------------------------------------------------------
# keyed consts cache
# ---------------------------------------------------------------------------


def test_consts_built_once_per_scenario_set():
    """Experiment.run/get_runner used to rebuild packed EngineConsts every
    call; registry-name scenario sets now build once per process."""
    consts_cache_clear()
    names = ["paper-fabric", "leaf-spine"]
    e1 = Experiment(scenarios=names, policies=POLICIES[:1])
    e1.build()
    e1.build()                                  # instance memo
    assert consts_build_count() == 1
    Experiment(scenarios=names, policies=POLICIES[:2]).build()
    assert consts_build_count() == 1            # cross-Experiment cache
    Experiment(scenarios="paper-fabric").build()
    assert consts_build_count() == 2            # different key -> new build

    # a consts-cache hit must also hit the compiled-runner cache: same
    # consts identity, same SimMeta -> zero extra traces
    runners.cache_clear()
    Experiment(scenarios=names, policies=POLICIES[:1]).run()
    t = runners.trace_count()
    Experiment(scenarios=names, policies=POLICIES[:1]).run()
    assert runners.trace_count() == t


def test_consts_cache_skips_failure_crosses():
    """Failure crosses mutate the setups after build — never cached."""
    from repro.scenarios.failures import failure_injector
    consts_cache_clear()
    for _ in range(2):
        Experiment(scenarios="paper-fabric",
                   failures=failure_injector(host_rate=0.05)).build()
    assert consts_build_count() == 2
