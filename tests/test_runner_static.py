"""The batched runners close over uniform branch fields (DESIGN.md §6):
a ``STATIC_FIELDS`` entry that one host value holds across the batch is
compiled in as a Python int, by the fleet's rule.  The specialised
programs match the generic one bit for bit, cache under their signature,
and a batch whose every static field varies keeps the generic program."""
import jax
import pytest

from conftest import assert_states_equal as assert_states_identical
from conftest import tiny_setups
from repro.api import Experiment, PolicyConfig, runners
from repro.core import (PLACE_LEAST_USED, PLACE_RANDOM, PLACE_ROUND_ROBIN,
                        ROUTE_LEGACY, ROUTE_SDN)
from repro.core.fairshare import TRAFFIC_FAIRSHARE, TRAFFIC_WATERFILL

BATCHES = {
    # the paper-sweep shape: routing varies, traffic and placement do not
    "eq3_mixed_routing": (
        [PolicyConfig(routing=ROUTE_SDN), PolicyConfig(routing=ROUTE_LEGACY)],
        (None, TRAFFIC_FAIRSHARE, PLACE_LEAST_USED)),
    "eq3_uniform": (
        [PolicyConfig(placement=PLACE_RANDOM, seed=s) for s in (0, 1)],
        (ROUTE_SDN, TRAFFIC_FAIRSHARE, PLACE_RANDOM)),
    "waterfill_uniform": (
        [PolicyConfig(traffic=TRAFFIC_WATERFILL, routing=r,
                      job_concurrency=2)
         for r in (ROUTE_SDN, ROUTE_LEGACY)],
        (None, TRAFFIC_WATERFILL, PLACE_LEAST_USED)),
    # every static field varies: today's generic program
    "all_mixed": (
        [PolicyConfig(traffic=TRAFFIC_WATERFILL, routing=ROUTE_SDN,
                      placement=PLACE_ROUND_ROBIN),
         PolicyConfig(traffic=TRAFFIC_FAIRSHARE, routing=ROUTE_LEGACY,
                      placement=PLACE_LEAST_USED)],
        runners.GENERIC),
}


def _generic(meta, kind, consts, pols):
    """The program every batch compiled before: no field closed over."""
    fn, init = runners._make_fn(meta, kind, counted=False)
    return jax.jit(fn)(consts, pols, init(consts, pols))


@pytest.mark.parametrize("kind", ["policy_batch", "grid"])
@pytest.mark.parametrize("batch", list(BATCHES))
def test_specialised_batch_matches_generic(kind, batch):
    policies, sig = BATCHES[batch]
    setups = tiny_setups()
    exp = Experiment(scenarios=setups[:1] if kind == "policy_batch"
                     else setups, policies=policies)
    consts, meta = exp.build()
    assert runners.static_signature(
        {**exp.policy_arrays(), **exp._static_policies()}) == sig
    runners.cache_clear()
    got = exp.run().states
    specialised = int(sig != runners.GENERIC)
    assert runners.dispatch_counts() == {"specialised": specialised,
                                         "generic": 1 - specialised}
    want = _generic(meta, kind, consts, exp.policy_arrays())
    if kind == "policy_batch":
        want = jax.tree_util.tree_map(lambda a: a[None], want)
    assert_states_identical(got, want, f"{kind}/{batch}: ")


def test_specialised_programs_cache_by_signature():
    """One trace per (meta, kind, sig): a repeat call is trace-free, a new
    uniform signature traces once more, and device-array policies (the
    deprecated shims) keep the generic program."""
    runners.cache_clear()
    setup = tiny_setups()[0][1]
    sweep = [PolicyConfig(routing=ROUTE_SDN),
             PolicyConfig(routing=ROUTE_LEGACY)]
    r1 = Experiment(scenarios=setup, policies=sweep).run()
    assert runners.trace_count() == 1
    r2 = Experiment(scenarios=setup, policies=sweep).run()
    assert runners.trace_count() == 1
    assert_states_identical(r1.states, r2.states)

    Experiment(scenarios=setup, policies=[
        p.replace(traffic=TRAFFIC_WATERFILL) for p in sweep]).run()
    assert runners.trace_count() == 2
    assert runners.dispatch_counts() == {"specialised": 3, "generic": 0}

    exp = Experiment(scenarios=setup, policies=sweep)
    consts, meta = exp.build()
    runners.get_runner(meta, "policy_batch")(consts, exp.policy_arrays())
    assert runners.trace_count() == 3
    assert runners.dispatch_counts() == {"specialised": 3, "generic": 1}
