"""Streaming engine tests (DESIGN.md §11).

The load-bearing guarantee: a finite trace that fits the ring's slot
capacity runs BIT-IDENTICALLY to ``Experiment.run`` on the equivalent
``ring_setup`` — the streaming layer adds refills around the compiled
chunk program, it never changes what the engine computes.  Plus: the
refill path conserves every arrival, and a large open-arrival run at
fixed slot capacity completes in bounded memory (slow-marked).
"""
import numpy as np
import pytest

from simhelpers import assert_states_equal
from invariants import check_stream
from repro.api import Experiment
from repro.core.policies import (PLACE_ROUND_ROBIN, PolicyConfig,
                                 ROUTE_LEGACY, ROUTE_SDN, TRAFFIC_WATERFILL)
from repro.core.streaming import RingSpec, ring_setup
from repro.scenarios import get_scenario
from repro.scenarios.arrivals import (PoissonArrivals, ServiceClass,
                                      TraceArrivals)
from repro.scenarios.workloads import JobTemplate

POLICIES = [
    ("sdn", PolicyConfig(routing=ROUTE_SDN, job_concurrency=2)),
    ("legacy", PolicyConfig(routing=ROUTE_LEGACY, job_concurrency=2,
                            placement=PLACE_ROUND_ROBIN)),
    ("wfill", PolicyConfig(routing=ROUTE_SDN, traffic=TRAFFIC_WATERFILL,
                           seed=1)),
]


@pytest.mark.parametrize("scen,seed", [
    ("leaf-spine", 0), ("leaf-spine", 1),
    ("paper-fabric-ctrl", 0), ("leaf-spine-failures", 1),
])
def test_finite_trace_bit_identity(scen, seed):
    """A trace that fits the slots (zero refills) reproduces
    ``Experiment.run`` on the same ring setup BITWISE, for every policy —
    across plain / ctrl / failure scenarios x workload seeds."""
    kw = dict(split=1) if scen.startswith("paper") else dict(n_jobs=3)
    setup = get_scenario(scen, seed=seed, **kw).build()
    horizon = 1e9
    arrivals = TraceArrivals(jobs=tuple(setup.jobs))
    jobs = [a.job for a in arrivals.events(horizon)]   # submit-time order
    spec = RingSpec.for_jobs(jobs, slots=len(jobs))

    exp = Experiment(scenarios=(scen, setup), policies=POLICIES)
    res = exp.run_stream(arrivals, horizon, slots=len(jobs),
                         return_states=True)
    assert res.stats.refills == 0          # the trace fit the ring

    rs = ring_setup(jobs, setup.cluster, spec, route_table=setup.route_table,
                    failures=setup.failures, ctrl=setup.ctrl)
    ref = Experiment(scenarios=("ring", rs), policies=POLICIES).run()
    for pi, (pname, _) in enumerate(POLICIES):
        assert_states_equal(ref.state(0, pi), res.final_states[pi],
                            f"{scen}/seed{seed}/{pname}")


def test_refill_conserves_arrivals():
    """A trace LONGER than the ring recycles slots; every arrival is loaded
    and retired exactly once per lane and sojourns are sane."""
    setup = get_scenario("leaf-spine", n_jobs=2).build()
    times = tuple(3.0 * i for i in range(12))
    arrivals = TraceArrivals(
        times=times,
        classes=(ServiceClass("only", slo_s=500.0,
                              template=JobTemplate(n_map=2, n_reduce=1)),))
    exp = Experiment(scenarios=("leaf-spine", setup), policies=POLICIES[:2])
    res = exp.run_stream(arrivals, horizon=40.0, slots=4, chunk_steps=64)
    assert res.stats.trace_len == sum(1 for t in times if t < 40.0)
    assert res.stats.refills > 0
    check_stream(res, label="refill")
    for pi in range(res.n_policies):
        j = res.jobs[pi]
        assert np.all(j["sojourn"] > 0)
        # arrival order is preserved in the per-lane load order: job k
        # cannot be admitted before it arrived
        assert np.all(j["t_admit"] >= j["t_arr"] - 1e-4)


def test_windowed_metrics_shape_and_nan_masking():
    """Windows cover every completion; empty windows are NaN (not 0) for
    percentile metrics and SLO attainment, 0 for counts."""
    setup = get_scenario("leaf-spine", n_jobs=2).build()
    arrivals = PoissonArrivals(
        rate=0.12, seed=4,
        classes=(ServiceClass("a", slo_s=100.0, share=0.5),
                 ServiceClass("b", slo_s=30.0, share=0.5, weight=1.0)))
    exp = Experiment(scenarios=("leaf-spine", setup), policies=POLICIES[:1])
    res = exp.run_stream(arrivals, horizon=150.0, warmup=30.0, window=25.0,
                         slots=4)
    wd = res.windows(0)
    n_w = wd["t0"].size
    assert wd["slo_attainment"].shape == (2, n_w)
    assert wd["t1"][-1] >= max(res.horizon, float(res.jobs[0]["t_done"].max()))
    empty = wd["n_done"] == 0
    assert np.all(np.isnan(wd["p99_sojourn_s"][empty]))
    assert np.all(wd["throughput_jobs_s"][empty] == 0.0)
    done = wd["n_done"] > 0
    assert np.all(wd["p50_sojourn_s"][done] <= wd["p99_sojourn_s"][done])
    att = wd["slo_attainment"]
    assert np.all((att[np.isfinite(att)] >= 0) & (att[np.isfinite(att)] <= 1))
    # summary excludes the warmup
    sm = res.summary(0)
    n_after = int((res.jobs[0]["t_done"] >= 30.0).sum())
    assert sm["jobs_done"] == n_after
    assert set(sm["classes"]) == {"a", "b"}
    # rows() is the flat export of the same windows
    rows = [r for r in res.rows() if r["policy"] == res.policy_names[0]]
    assert len(rows) == n_w and "slo_a" in rows[0] and "slo_b" in rows[0]


def test_ring_spec_rejects_oversize_job():
    setup = get_scenario("leaf-spine", n_jobs=2).build()
    big = TraceArrivals(
        times=(1.0,),
        classes=(ServiceClass("big",
                              template=JobTemplate(n_map=9, n_reduce=3)),))
    spec = RingSpec(slots=2, n_map_max=2, n_reduce_max=1)
    exp = Experiment(scenarios=("leaf-spine", setup), policies=POLICIES[:1])
    with pytest.raises(ValueError, match="slot geometry"):
        exp.run_stream(big, horizon=10.0, spec=spec)


@pytest.mark.slow
def test_large_open_arrival_bounded_memory():
    """Acceptance: a >=100k-job open-arrival run at FIXED slot capacity
    completes — tensor shapes never grow with the trace — and produces
    warmup-excluded windowed metrics."""
    setup = get_scenario("leaf-spine", n_spine=2, n_leaf=2, hosts_per_leaf=2,
                         n_jobs=2).build()
    tiny = JobTemplate(n_map=1, n_reduce=1, map_mi=300.0, reduce_mi=300.0,
                       input_gbits=0.02, shuffle_gbits=0.01,
                       output_gbits=0.01)
    arrivals = PoissonArrivals(
        rate=120.0, seed=7,
        classes=(ServiceClass("t", slo_s=20.0, template=tiny,
                              scale_lo=1.0, scale_hi=1.0),))
    horizon = 100_000 / 120.0 * 1.05        # ~105k expected arrivals
    exp = Experiment(scenarios=("leaf-spine", setup),
                     policies=[("sdn", PolicyConfig(routing=ROUTE_SDN,
                                                    job_concurrency=64))])
    res = exp.run_stream(arrivals, horizon, warmup=60.0, window=60.0,
                         slots=64, chunk_steps=512)
    assert res.stats.trace_len >= 100_000
    check_stream(res, label="100k")
    sm = res.summary(0)
    assert sm["jobs_done"] > 90_000
    assert np.isfinite(sm["p99_sojourn_s"])
    assert np.isfinite(sm["throughput_jobs_s"])
