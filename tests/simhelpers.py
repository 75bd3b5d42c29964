"""Helpers shared by the test files (a plain module, not ``conftest``:
bench/tests has a conftest of its own, and one pytest session holds only
one module of that name)."""
import dataclasses

import numpy as np


def with_failures(setup, sched):
    """A copy of ``setup`` carrying the given FailureSchedule."""
    return dataclasses.replace(setup, failures=sched)


def with_ctrl(setup, cfg):
    """A copy of ``setup`` carrying the given CtrlPlaneConfig."""
    return dataclasses.replace(setup, ctrl=cfg)


def with_degradation(setup, sched, spec_slots=None):
    """A copy of ``setup`` carrying the given DegradationSchedule (and
    optionally clone capacity for the speculation axis)."""
    kw = {"degradation": sched}
    if spec_slots is not None:
        kw["spec_slots"] = spec_slots
    return dataclasses.replace(setup, **kw)


def dims(setup):
    """-> (n_hosts, n_links) of the setup's topology (FailureSchedule
    constructor args)."""
    topo = setup.cluster.topo
    return topo.n_hosts, topo.n_links


def tiny_setups():
    """Two tiny heterogeneous scenarios for packed-sweep tests."""
    from repro.core.mapreduce import build_setup
    from repro.core.topology import canonical_tree, leaf_spine
    from repro.scenarios import make_cluster, uniform_workload, zipf_workload
    ls = build_setup(uniform_workload(n_jobs=2, seed=0),
                     make_cluster(leaf_spine(2, 2, 2)), k_max=4)
    ct = build_setup(zipf_workload(n_jobs=3, seed=1),
                     make_cluster(canonical_tree(2, 2, 2)), k_max=4)
    return [("leaf-spine", ls), ("canon-tree", ct)]


def assert_states_equal(a, b, label=""):
    """Leaf-by-leaf bit equality (NaN == NaN) between two SimStates."""
    for name in a._fields:
        la = np.asarray(getattr(a, name))
        lb = np.asarray(getattr(b, name))
        assert la.shape == lb.shape, \
            f"{label}: SimState.{name} shape {la.shape} != {lb.shape}"
        assert np.array_equal(la, lb, equal_nan=True), \
            f"{label}: SimState.{name} values differ"
