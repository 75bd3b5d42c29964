"""Fair-share bandwidth and control-plane properties (hypothesis; skipped
when the optional dev dependency is absent — see requirements-dev.txt)."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings
from hypothesis import strategies as st

import jax

from repro.core import (CtrlPlaneConfig, INSTALL_PROACTIVE, INSTALL_REACTIVE,
                        PolicyConfig, fairshare, simulate)
from repro.core.fairshare import eq3_rates, waterfill_rates
from repro.kernels.eq3_bottleneck.ops import eq3_bottleneck
from repro.kernels.eq3_bottleneck.ref import eq3_bottleneck_ref
from repro.core.flows import Flow, flows_setup
from repro.core.topology import leaf_spine

INTRA = 1e12


def _random_instance(draw):
    n_links = draw(st.integers(2, 8))
    n_flows = draw(st.integers(1, 10))
    max_hops = draw(st.integers(1, 4))
    bw = np.array([draw(st.floats(0.5, 10.0)) for _ in range(n_links)],
                  np.float32)
    routes = np.full((n_flows, max_hops), -1, np.int32)
    for f in range(n_flows):
        hops = draw(st.integers(1, min(max_hops, n_links)))
        links = draw(st.lists(st.integers(0, n_links - 1), min_size=hops,
                              max_size=hops, unique=True))
        routes[f, :hops] = links
    active = np.array([draw(st.booleans()) for _ in range(n_flows)])
    return bw, routes, active


@st.composite
def instances(draw):
    return _random_instance(draw)


def link_loads(routes, rates, n_links):
    load = np.zeros(n_links)
    for f in range(routes.shape[0]):
        for li in routes[f]:
            if li >= 0:
                load[li] += rates[f]
    return load


@given(instances())
@settings(max_examples=60, deadline=None)
def test_eq3_never_oversubscribes(inst):
    bw, routes, active = inst
    rates = np.asarray(eq3_rates(jnp.asarray(routes), jnp.asarray(active),
                                 jnp.asarray(bw), INTRA))
    assert np.all(rates[~active] == 0)
    load = link_loads(routes, rates, bw.shape[0])
    assert np.all(load <= bw * (1 + 1e-4))


@given(instances())
@settings(max_examples=60, deadline=None)
def test_waterfill_no_oversubscribe_and_saturation(inst):
    bw, routes, active = inst
    rates = np.asarray(waterfill_rates(jnp.asarray(routes),
                                       jnp.asarray(active),
                                       jnp.asarray(bw), INTRA))
    load = link_loads(routes, rates, bw.shape[0])
    assert np.all(load <= bw * (1 + 1e-3))
    # max-min: every active flow crosses at least one (nearly) saturated
    # link — otherwise its rate could grow (Pareto violation)
    for f in range(routes.shape[0]):
        if not active[f] or routes[f].max() < 0:
            continue
        sat = False
        for li in routes[f]:
            if li >= 0 and load[li] >= bw[li] * (1 - 1e-2):
                sat = True
        assert sat, f"flow {f} not bottlenecked anywhere"


@given(instances())
@settings(max_examples=40, deadline=None)
def test_waterfill_total_throughput_geq_eq3(inst):
    bw, routes, active = inst
    r3 = np.asarray(eq3_rates(jnp.asarray(routes), jnp.asarray(active),
                              jnp.asarray(bw), INTRA))
    rw = np.asarray(waterfill_rates(jnp.asarray(routes),
                                    jnp.asarray(active),
                                    jnp.asarray(bw), INTRA))
    assert rw.sum() >= r3.sum() * (1 - 1e-3)


@given(instances(), st.integers(0, 3))
@settings(max_examples=60, deadline=None)
def test_capacity_invariant_both_policies_any_iter_cap(inst, n_iter):
    """The engine-facing invariant: under BOTH traffic policies the summed
    allocation on every link stays within its bandwidth — including when
    the water-fill iteration cap leaves flows unfrozen and the clamped
    fallback kicks in (the old Eq. 3 fallback stacked full-capacity rates
    on top of frozen allocations and oversubscribed shared links)."""
    bw, routes, active = inst
    for rates in (
            eq3_rates(jnp.asarray(routes), jnp.asarray(active),
                      jnp.asarray(bw), INTRA),
            waterfill_rates(jnp.asarray(routes), jnp.asarray(active),
                            jnp.asarray(bw), INTRA),
            # force the iteration-cap fallback path
            waterfill_rates(jnp.asarray(routes), jnp.asarray(active),
                            jnp.asarray(bw), INTRA, n_iter=n_iter)):
        load = link_loads(routes, np.asarray(rates), bw.shape[0])
        assert np.all(load <= bw * (1 + 1e-3)), (load, bw)


# ---------------------------------------------------------------------------
# the Eq. 3 bottleneck kernel (the TPU's lookup) against the gather
# ---------------------------------------------------------------------------


def _eq3_instance(rng, n_links, n_pkts, values, active):
    """Link bandwidths with dead links (0), channel counts, routes of 0-6
    hops padded with -1 (empty routes included), and an active mask."""
    if values == "range":     # every binade the three-way bf16 split carries
        bw = rng.uniform(1, 2, n_links) * 2.0 ** rng.integers(-100, 120,
                                                                n_links)
    else:                     # what the fabrics hold: bits/s
        bw = rng.choice([1e9, 4e9], n_links)
    bw = bw.astype(np.float32)
    bw[rng.random(n_links) < 0.1] = 0.0
    nc = rng.integers(0, 40, n_links).astype(np.int32)
    routes = rng.integers(0, n_links, (n_pkts, 6)).astype(np.int32)
    routes[np.arange(6)[None, :] >= rng.integers(0, 7, n_pkts)[:, None]] = -1
    act = (rng.random(n_pkts) < 0.7) if active else np.zeros(n_pkts, bool)
    return bw, nc, routes, act


def _eq3_path(interpret, bw, nc, routes, act, width):
    """Eq. 3 through the interpreted kernel, or the CPU's own path (the
    gather)."""
    prev = fairshare._INTERPRET
    fairshare._INTERPRET = interpret
    try:
        f = lambda b, n, r, a: eq3_rates(r, a, b, INTRA, nc=n)  # noqa: E731
        if width is not None:
            f = jax.vmap(f)
        return np.asarray(jax.jit(f)(bw, nc, routes, act))
    finally:
        fairshare._INTERPRET = prev


# the two configurations' link counts (130: paper-usecase, 6,146:
# fat-tree-k16; neither a multiple of 128) at 6 hops, the packet axis cut
# for time at 6,146 links; lane widths as the fleet and the policy batch
# vmap them
@pytest.mark.parametrize("n_links,n_pkts,width,values,active", [
    (130, 460, None, "engine", True),
    (130, 460, 1, "range", True),
    (130, 460, 2, "engine", True),
    (130, 460, 16, "engine", True),
    (130, 460, 2, "engine", False),
    (6146, 300, None, "range", True),
    (6146, 300, 2, "engine", True),
    (128, 129, 2, "range", True),
])
def test_eq3_kernel_bit_identical_to_gather(n_links, n_pkts, width, values,
                                            active):
    rng = np.random.default_rng(n_links + n_pkts + (width or 0))
    lanes = [_eq3_instance(rng, n_links, n_pkts, values, active)
             for _ in range(width or 1)]
    args = [np.stack(a) if width is not None else a[0]
            for a in zip(*lanes)]
    before = fairshare.lookup_counts()
    got = _eq3_path(True, *args, width)
    want = _eq3_path(False, *args, width)
    after = fairshare.lookup_counts()
    assert after["kernel"] == before["kernel"] + 1
    assert after["gather"] == before["gather"] + 1
    assert np.array_equal(got.view(np.int32), want.view(np.int32))
    assert np.all(want[~args[3]] == 0)
    # the lookup alone (lane 0): +inf exactly where a route is empty
    bw, nc, routes, _ = lanes[0]
    share = bw / np.maximum(nc, 1).astype(np.float32)
    look = np.asarray(eq3_bottleneck(share, routes, interpret=True))
    ref = np.asarray(eq3_bottleneck_ref(share, routes))
    assert np.array_equal(look.view(np.int32), ref.view(np.int32))
    empty = np.all(routes < 0, axis=-1)
    assert empty.any() and np.all(np.isinf(look[empty]))


# ---------------------------------------------------------------------------
# control-plane properties (DESIGN.md §10)
# ---------------------------------------------------------------------------

# fixed topology + flow count: every draw reuses the same traced program
# (ctrl scalars live in consts; only table_slots changes the trace)
_CTRL_TOPO = leaf_spine(2, 2, 2)


def _ctrl_run(flows, cfg, install_mode=None):
    setup = flows_setup(_CTRL_TOPO, flows)
    if cfg.any_ctrl:
        setup = dataclasses.replace(setup, ctrl=cfg)
    pol = PolicyConfig() if install_mode is None else \
        PolicyConfig(install_mode=install_mode)
    return simulate(setup, pol)


@given(lat=st.floats(0.0, 0.6), rate=st.sampled_from([2.0, 10.0, 100.0]),
       slots=st.sampled_from([0, 2]),
       sizes=st.tuples(st.floats(1.0, 10.0), st.floats(1.0, 10.0)))
@settings(max_examples=15, deadline=None)
def test_controller_work_conservation(lat, rate, slots, sizes):
    """Flow-table conservation: every installed rule either still occupies
    a slot or was evicted — ``occupied == installs - evictions`` EXACTLY,
    for any (latency, rate, slots) config, including the table-less
    slots=0 degenerate."""
    cfg = CtrlPlaneConfig(install_latency=lat, ctrl_rate=rate,
                          table_slots=slots)
    s = _ctrl_run([Flow(0, 2, sizes[0]), Flow(1, 3, sizes[1])], cfg)
    assert not bool(s.stalled)
    installs = int(s.ctrl_installs)
    evictions = int(s.ctrl_evictions)
    occupied = int((np.asarray(s.ftab_pair) >= 0).sum())
    assert installs >= 0 and evictions >= 0
    assert occupied == installs - evictions
    if slots == 0:
        assert installs == evictions      # nothing can be retained


@given(lats=st.tuples(st.floats(0.0, 1.5), st.floats(0.0, 1.5)),
       rate=st.sampled_from([5.0, 50.0]),
       mode=st.sampled_from([INSTALL_REACTIVE, INSTALL_PROACTIVE]))
@settings(max_examples=15, deadline=None)
def test_install_latency_monotone(lats, rate, mode):
    """A slower controller can only delay a single flow: its completion
    time is non-decreasing in install latency under BOTH install modes
    (proactive pre-pins the route but still waits out the install)."""
    lo, hi = sorted(lats)
    t_lo = float(_ctrl_run(
        [Flow(0, 2, 8.0)], CtrlPlaneConfig(install_latency=lo,
                                           ctrl_rate=rate, table_slots=2),
        install_mode=mode).time)
    t_hi = float(_ctrl_run(
        [Flow(0, 2, 8.0)], CtrlPlaneConfig(install_latency=hi,
                                           ctrl_rate=rate, table_slots=2),
        install_mode=mode).time)
    assert t_hi >= t_lo - 1e-4
    # the latency is paid additively on an uncontended path
    assert t_hi - t_lo == pytest.approx(hi - lo, abs=1e-3)
