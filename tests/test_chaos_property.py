"""Hypothesis property for the §13 degradation off-switch (skipped when
the optional dev dependency is absent — see requirements-dev.txt).

The property: a ``DegradationSchedule`` whose every factor is 1.0 is
STRUCTURALLY inert — no matter where its windows sit, the run is bitwise
the no-schedule program.  This is stronger than the fixed-window unit
test in test_chaos.py: window placement must never leak into the trace
(inert windows are masked out of ``deg_breaks``), so there is no
"breakpoint at t but zero effect" drift either.
"""
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings
from hypothesis import strategies as st

from simhelpers import assert_states_equal, with_degradation
from repro.core import (PolicyConfig, no_degradation, simulate)
from repro.core.flows import Flow, flows_setup
from repro.core.mapreduce import build_setup
from repro.core.topology import leaf_spine
from repro.scenarios import make_cluster, uniform_workload

_TOPO = leaf_spine(2, 2, 2)
_SETUP = build_setup(uniform_workload(n_jobs=2, seed=0),
                     make_cluster(_TOPO), k_max=4)
_BASE = None


def _base():
    global _BASE
    if _BASE is None:
        _BASE = simulate(_SETUP, PolicyConfig(job_concurrency=2))
    return _BASE


@st.composite
def unity_schedules(draw):
    """Arbitrary window times, every factor pinned at 1.0."""
    n_h, n_l = _TOPO.n_hosts, _TOPO.n_links
    sched = no_degradation(n_h, n_l)
    for i in range(n_h):
        if draw(st.booleans()):
            at = draw(st.floats(0.0, 500.0, allow_nan=False))
            sched.host_slow_t[i] = at
            sched.host_restore_t[i] = at + draw(
                st.floats(0.1, 500.0, allow_nan=False))
    for i in range(n_l):
        if draw(st.booleans()):
            at = draw(st.floats(0.0, 500.0, allow_nan=False))
            sched.link_slow_t[i] = at
            sched.link_restore_t[i] = at + draw(
                st.floats(0.1, 500.0, allow_nan=False))
    return sched.validate(n_h, n_l)


@settings(max_examples=20, deadline=None)
@given(sched=unity_schedules())
def test_unity_factor_schedule_is_structurally_off(sched):
    assert not sched.any_degradation
    run = simulate(with_degradation(_SETUP, sched),
                   PolicyConfig(job_concurrency=2))
    assert_states_equal(_base(), run, "unity-degradation")


@settings(max_examples=10, deadline=None)
@given(at=st.floats(0.5, 6.0, allow_nan=False),
       factor=st.floats(0.05, 0.95, allow_nan=False))
def test_brownout_rate_arithmetic_property(at, factor):
    """For a single flow on one cable: brownout at ``at`` with ``factor``
    gives done-time = at + (total - at)/factor exactly (the flow runs 1
    unit/s healthy) — the piecewise-constant integration is analytic, not
    stepped."""
    from repro.core.topology import torus_2d
    from repro.core import link_brownout
    topo = torus_2d(2, 1, bw=1e9)
    setup = flows_setup(topo, [Flow(0, 1, 8.0)])
    sched = link_brownout(topo.n_hosts, topo.n_links, [0, 1], at=at,
                          factor=factor)
    s = simulate(with_degradation(setup, sched), PolicyConfig())
    expect = at + (8.0 - at) / factor
    assert float(s.time) == pytest.approx(expect, rel=1e-3)
