"""Bit-identity of the vectorized step kernel (DESIGN.md §8) against the
pre-vectorization scalar event loop.

The reference implementation below is a self-contained copy of the engine
as it stood before the batched-admission / compacted-activation / fused
network-pass rewrite: admission is an O(n_jobs) argmin fori, placement an
O(n_tasks) ordered fori, packet activation an O(n_packets) fori, every
network tensor is recomputed per phase, and ``_finished`` is evaluated
twice per loop iteration.  The suite runs BOTH kernels over every registry
scenario x a policy grid covering all placement/routing/recovery branches
(with job-selection, traffic and concurrency cycling through their values)
x 3 seeds, and asserts every ``SimState`` field is bitwise equal
(NaN == NaN) — the vectorized kernel must preserve the sequential
tie-break order exactly.
"""
import dataclasses
import itertools

import jax
import jax.numpy as jnp
import pytest

from simhelpers import assert_states_equal
from repro.core import fairshare
from repro.core.engine import (NODE_OFFSET, init_state_from_consts,
                               make_consts)
from repro.core.mapreduce import ACTIVE, DONE, WAITING
from repro.core.policies import (JOBSEL_FCFS, JOBSEL_PRIORITY, JOBSEL_SJF,
                                 PLACE_LEAST_USED, PLACE_RANDOM,
                                 PLACE_ROUND_ROBIN, PolicyConfig,
                                 RECOVERY_RESTART, RECOVERY_RESUME,
                                 ROUTE_LEGACY, ROUTE_SDN, TRAFFIC_FAIRSHARE,
                                 TRAFFIC_WATERFILL)
from repro.core.routing import (choose_route, flow_hash_u32,
                                route_candidates, route_ends,
                                route_links)
from repro.core.simmeta import SimMeta
from repro.api import runners
from repro.scenarios import get_scenario, list_scenarios
from repro.scenarios.sweep import pack_setups, policy_arrays

_INF = jnp.float32(jnp.inf)

# ---------------------------------------------------------------------------
# reference kernel: the pre-PR scalar event loop, verbatim semantics
# ---------------------------------------------------------------------------


def _ref_link_bw(c, meta, s):
    if meta.has_failures:
        return jnp.where(s.link_dead, 0.0, c.link_bw)
    return c.link_bw


def _ref_route_links(c, meta, s, mask):
    pair = jnp.maximum(s.pkt_pair, 0)
    cand = jnp.maximum(s.pkt_cand, 0)
    ends = route_ends(c, pair // meta.n_nodes, pair % meta.n_nodes)
    links = route_links(c, ends, cand)
    return jnp.where(mask[:, None], links, -1)


def _ref_endpoints(c, s):
    n_tasks = s.task_vm.shape[0]

    def node_of(task_idx):
        t = jnp.clip(task_idx, 0, n_tasks - 1)
        vm = jnp.maximum(s.task_vm[t], 0)
        node = jnp.where(task_idx < 0, c.storage_node, c.vm_host[vm])
        return jnp.where(task_idx >= NODE_OFFSET,
                         task_idx - NODE_OFFSET, node).astype(jnp.int32)
    return node_of(c.pkt_src_task), node_of(c.pkt_dst_task)


def _ref_apply_failures(c, meta, pol, s):
    t = s.time
    host_dead = (c.host_fail_t <= t) & (t < c.host_recover_t)
    link_dead = (c.link_fail_t <= t) & (t < c.link_recover_t)
    new_h = host_dead & ~s.host_dead
    new_l = link_dead & ~s.link_dead
    restart = pol["recovery"] == RECOVERY_RESTART

    n_hosts_pad = c.host_fail_t.shape[0]
    src_node, dst_node = _ref_endpoints(c, s)
    p_active = s.pkt_state == ACTIVE
    links = _ref_route_links(c, meta, s, p_active)
    route_hit = p_active & jnp.any(
        (links >= 0) & new_l[jnp.maximum(links, 0)], axis=-1)

    def _endpoint_died(node):
        return (node < c.n_hosts) & new_h[jnp.clip(node, 0, n_hosts_pad - 1)]

    ep_hit = p_active & (_endpoint_died(src_node) | _endpoint_died(dst_node))
    hit_p = route_hit | ep_hit
    pkt_state = jnp.where(hit_p, WAITING, s.pkt_state)
    pkt_rem = jnp.where(ep_hit & restart, c.pkt_bits.astype(jnp.float32),
                        s.pkt_rem)
    pkt_pair = jnp.where(hit_p, -1, s.pkt_pair)
    pkt_cand = jnp.where(hit_p, -1, s.pkt_cand)
    pkt_reroutes = s.pkt_reroutes + hit_p.astype(jnp.int32)

    vm_safe = jnp.maximum(s.task_vm, 0)
    task_host = jnp.clip(c.vm_host[vm_safe], 0, n_hosts_pad - 1)
    hit_t = (c.task_valid & (s.task_vm >= 0) & new_h[task_host]
             & ((s.task_state == ACTIVE) | (s.task_state == WAITING)))
    task_state = jnp.where(hit_t, WAITING, s.task_state)
    task_rem = jnp.where(hit_t & restart, c.task_mi.astype(jnp.float32),
                         s.task_rem)
    task_start = jnp.where(hit_t, jnp.nan, s.task_start)
    vm_load = s.vm_load.at[vm_safe].add(-hit_t.astype(jnp.int32))
    task_vm = jnp.where(hit_t, -1, s.task_vm)
    task_restarts = s.task_restarts + hit_t.astype(jnp.int32)

    return s._replace(
        host_dead=host_dead, link_dead=link_dead,
        pkt_state=pkt_state, pkt_rem=pkt_rem, pkt_pair=pkt_pair,
        pkt_cand=pkt_cand, pkt_reroutes=pkt_reroutes,
        task_state=task_state, task_rem=task_rem, task_start=task_start,
        task_vm=task_vm, vm_load=vm_load, task_restarts=task_restarts)


def _ref_admit_and_place(c, meta, pol, s):
    n_vms = c.n_vms
    vm_slot_live = jnp.arange(meta.n_vms) < n_vms
    if meta.has_failures:
        vm_live = vm_slot_live & ~s.host_dead[
            jnp.clip(c.vm_host, 0, c.host_fail_t.shape[0] - 1)]
        n_live = jnp.sum(vm_live.astype(jnp.int32))
        live_pos = jnp.cumsum(vm_live.astype(jnp.int32)) - 1
    else:
        vm_live, n_live, live_pos = vm_slot_live, n_vms, None

    def pick_vm(vm_load, counter, h):
        masked_load = jnp.where(vm_live, vm_load, jnp.iinfo(jnp.int32).max)
        if meta.has_failures:
            def kth_live(k):
                return jnp.argmax(vm_live & (live_pos == k)).astype(jnp.int32)
            rr = kth_live(counter % jnp.maximum(n_live, 1))
            rnd = kth_live(h % jnp.maximum(n_live, 1))
        else:
            rr, rnd = counter % n_vms, h % n_vms
        pick = jnp.where(
            pol["placement"] == PLACE_ROUND_ROBIN, rr,
            jnp.where(pol["placement"] == PLACE_RANDOM, rnd,
                      jnp.argmin(masked_load).astype(jnp.int32)))
        return pick.astype(jnp.int32)

    def place_mask(s, mine):
        def place_one(t, carry):
            vm_load, task_vm, counter = carry
            is_mine = mine[t]
            h = flow_hash_u32(jnp.int32(t), c.task_job[t], pol["seed"])
            pick = pick_vm(vm_load, counter, h)
            vm_load = jnp.where(is_mine, vm_load.at[pick].add(1), vm_load)
            task_vm = jnp.where(is_mine, task_vm.at[t].set(pick), task_vm)
            counter = counter + jnp.where(is_mine, 1, 0)
            return vm_load, task_vm, counter

        vm_load, task_vm, counter = jax.lax.fori_loop(
            0, s.task_vm.shape[0], place_one,
            (s.vm_load, s.task_vm, s.place_counter))
        return s._replace(vm_load=vm_load, task_vm=task_vm,
                          place_counter=counter)

    def admit_one(_, s):
        released = (~s.job_admitted) & c.job_valid & (c.job_release <= s.time)
        running = s.job_admitted & (s.job_out_done < c.job_n_out) & c.job_valid
        free = jnp.sum(running.astype(jnp.int32)) < pol["job_concurrency"]
        any_wait = jnp.any(released)
        key = jnp.where(
            pol["job_selection"] == JOBSEL_SJF, c.job_total_mi,
            jnp.where(pol["job_selection"] == JOBSEL_PRIORITY,
                      -c.job_priority, c.job_release))
        key = jnp.where(released, key, _INF)
        j = jnp.argmin(key).astype(jnp.int32)
        do = free & any_wait
        if meta.has_failures:
            do = do & (n_live > 0)

        def place(s):
            s = place_mask(s, (c.task_job == j) & c.task_valid)
            return s._replace(
                job_admitted=s.job_admitted.at[j].set(True),
                job_admit_t=s.job_admit_t.at[j].set(s.time))

        return jax.lax.cond(do, place, lambda s: s, s)

    s = jax.lax.fori_loop(0, s.job_admitted.shape[0], admit_one, s)

    if meta.has_failures:
        orphaned = (c.task_valid & (s.task_vm < 0)
                    & (s.task_state == WAITING)
                    & s.job_admitted[jnp.maximum(c.task_job, 0)]
                    & (n_live > 0))
        s = jax.lax.cond(jnp.any(orphaned),
                         lambda s: place_mask(s, orphaned), lambda s: s, s)
    return s


def _ref_activate(c, meta, pol, s):
    t_ready = ((s.task_state == WAITING) & (s.task_got >= c.task_need)
               & (s.task_vm >= 0))
    task_state = jnp.where(t_ready, ACTIVE, s.task_state)
    task_start = jnp.where(t_ready, s.time, s.task_start)
    s = s._replace(task_state=task_state, task_start=task_start)

    gate = c.pkt_gate_task
    gate_ok = jnp.where(gate < 0, True,
                        s.task_state[jnp.maximum(gate, 0)] == DONE)
    admitted = s.job_admitted[jnp.maximum(c.pkt_job, 0)]
    p_ready = (s.pkt_state == WAITING) & admitted & gate_ok & c.pkt_valid
    src_node, dst_node = _ref_endpoints(c, s)
    n_nodes = meta.n_nodes
    pair_all = (src_node * n_nodes + dst_node).astype(jnp.int32)
    reachable = ((route_ends(c, src_node, dst_node).n_cand > 0)
                 | (src_node == dst_node))
    p_ready = p_ready & reachable
    if meta.has_failures:
        n_tasks = s.task_vm.shape[0]

        def _ep_placed(ref):
            is_task = (ref >= 0) & (ref < NODE_OFFSET)
            return jnp.where(is_task,
                             s.task_vm[jnp.clip(ref, 0, n_tasks - 1)] >= 0,
                             True)

        p_ready = (p_ready & _ep_placed(c.pkt_src_task)
                   & _ep_placed(c.pkt_dst_task))

    link_bw = _ref_link_bw(c, meta, s)
    ch0 = fairshare.channel_counts(
        _ref_route_links(c, meta, s, s.pkt_state == ACTIVE),
        s.pkt_state == ACTIVE, meta.n_links)

    def act_one(i, carry):
        pkt_state, pkt_pair, pkt_cand, pkt_links, pkt_start, ch = carry
        ready = p_ready[i]
        pair = (src_node[i] * n_nodes + dst_node[i]).astype(jnp.int32)
        fh = flow_hash_u32(c.pkt_src_task[i] + 1, c.pkt_dst_task[i] + 1,
                           pol["seed"])
        ends = route_ends(c, src_node[i], dst_node[i])
        routes_k = route_candidates(c, ends)
        cand = choose_route(pol["routing"], routes_k, ends.n_cand,
                            link_bw, ch, fh)
        links = routes_k[cand]
        valid = links >= 0
        ch_new = ch.at[jnp.maximum(links, 0)].add(valid.astype(jnp.int32))
        if meta.has_failures:
            start_val = jnp.where(jnp.isnan(pkt_start[i]), s.time,
                                  pkt_start[i])
        else:
            start_val = s.time
        return (
            jnp.where(ready, pkt_state.at[i].set(ACTIVE), pkt_state),
            jnp.where(ready, pkt_pair.at[i].set(pair), pkt_pair),
            jnp.where(ready, pkt_cand.at[i].set(cand), pkt_cand),
            jnp.where(ready, pkt_links.at[i].set(links), pkt_links),
            jnp.where(ready, pkt_start.at[i].set(start_val), pkt_start),
            jnp.where(ready, ch_new, ch),
        )

    pkt_state, pkt_pair, pkt_cand, pkt_links, pkt_start, _ = \
        jax.lax.fori_loop(0, s.pkt_state.shape[0], act_one,
                          (s.pkt_state, s.pkt_pair, s.pkt_cand, s.pkt_links,
                           s.pkt_start, ch0))
    return s._replace(pkt_state=pkt_state, pkt_pair=pkt_pair,
                      pkt_cand=pkt_cand, pkt_links=pkt_links,
                      pkt_start=pkt_start)


def _ref_rates(c, meta, pol, s):
    p_active = s.pkt_state == ACTIVE
    links = _ref_route_links(c, meta, s, p_active)
    pkt_rate = fairshare.rates(pol["traffic"], links, p_active,
                               _ref_link_bw(c, meta, s), meta.intra_bw)
    t_active = s.task_state == ACTIVE
    vm = jnp.maximum(s.task_vm, 0)
    n_on_vm = jnp.zeros_like(c.vm_total_mips, jnp.int32).at[vm].add(
        t_active.astype(jnp.int32))
    share = c.vm_total_mips[vm] / jnp.maximum(n_on_vm[vm],
                                              1).astype(jnp.float32)
    task_rate = jnp.where(t_active, jnp.minimum(c.vm_core_mips[vm], share),
                          0.0)
    if meta.has_failures:
        task_rate = jnp.where(
            s.host_dead[jnp.clip(c.vm_host[vm], 0,
                                 c.host_fail_t.shape[0] - 1)],
            0.0, task_rate)
    return pkt_rate, task_rate, links, p_active, t_active


def _ref_finished(c, meta, s):
    all_done = jnp.all(~c.job_valid | (s.job_out_done >= c.job_n_out))
    return all_done | s.stalled | (s.steps >= meta.max_steps)


def _ref_step(c, meta, pol, s):
    from repro.core.energy import host_power, switch_power
    if meta.has_failures:
        s = _ref_apply_failures(c, meta, pol, s)
    s = _ref_admit_and_place(c, meta, pol, s)
    s = _ref_activate(c, meta, pol, s)
    pkt_rate, task_rate, links, p_active, t_active = _ref_rates(
        c, meta, pol, s)

    dt_p = jnp.min(jnp.where(p_active & (pkt_rate > 0),
                             s.pkt_rem / pkt_rate, _INF))
    dt_t = jnp.min(jnp.where(t_active & (task_rate > 0),
                             s.task_rem / task_rate, _INF))
    future = (~s.job_admitted) & c.job_valid & (c.job_release > s.time)
    dt_r = jnp.min(jnp.where(future, c.job_release - s.time, _INF))
    dt = jnp.minimum(jnp.minimum(dt_p, dt_t), dt_r)
    if meta.has_failures:
        def _next(ts):
            return jnp.min(jnp.where(ts > s.time, ts - s.time, _INF))

        dt_f = jnp.minimum(
            jnp.minimum(_next(c.host_fail_t), _next(c.host_recover_t)),
            jnp.minimum(_next(c.link_fail_t), _next(c.link_recover_t)))
        dt = jnp.minimum(dt, dt_f)
    stalled = jnp.isinf(dt)
    dt = jnp.where(stalled, 0.0, dt)

    vm_safe = jnp.maximum(s.task_vm, 0)
    host_of_task = c.vm_host[vm_safe]
    mips_used = jnp.zeros_like(c.host_total_mips).at[host_of_task].add(
        jnp.where(t_active, task_rate, 0.0))
    util = jnp.clip(mips_used / jnp.maximum(c.host_total_mips, 1e-9),
                    0.0, 1.0)
    if meta.has_failures:
        util = jnp.where(s.host_dead, 0.0, util)
    host_energy = s.host_energy + host_power(util, meta.energy) * dt
    host_busy = s.host_busy + jnp.where(util > 0, dt, 0.0)
    ch = fairshare.channel_counts(links, p_active, meta.n_links)
    live_link = (ch > 0).astype(jnp.int32)
    if meta.has_failures:
        live_link = jnp.where(s.link_dead, 0, live_link)
    node_ports = jnp.zeros(meta.n_nodes, jnp.int32)
    node_ports = node_ports.at[c.link_src].add(live_link)
    node_ports = node_ports.at[c.link_dst].add(live_link)
    sw_ports = jax.lax.dynamic_slice_in_dim(node_ports, meta.n_hosts,
                                            meta.n_switches)
    switch_energy = s.switch_energy + switch_power(sw_ports, meta.energy) * dt

    if meta.has_failures:
        n_j = s.job_downtime.shape[0]
        prog_t = ((t_active & (task_rate > 0) & c.task_valid)
                  .astype(jnp.int32))
        prog_p = ((p_active & (pkt_rate > 0) & c.pkt_valid)
                  .astype(jnp.int32))
        job_prog = jnp.zeros(n_j, jnp.int32)
        job_prog = job_prog.at[jnp.maximum(c.task_job, 0)].max(prog_t)
        job_prog = job_prog.at[jnp.maximum(c.pkt_job, 0)].max(prog_p)
        job_live = (s.job_admitted & (s.job_out_done < c.job_n_out)
                    & c.job_valid)
        job_downtime = s.job_downtime + jnp.where(
            job_live & (job_prog == 0), dt, 0.0)
    else:
        job_downtime = s.job_downtime

    time = s.time + dt
    pkt_rem = jnp.where(p_active, s.pkt_rem - pkt_rate * dt, s.pkt_rem)
    task_rem = jnp.where(t_active, s.task_rem - task_rate * dt, s.task_rem)
    pkt_tol = c.pkt_bits * 1e-6 + 1.0
    task_tol = c.task_mi * 1e-6 + 1e-6
    p_done_now = p_active & (pkt_rem <= pkt_tol)
    t_done_now = t_active & (task_rem <= task_tol)

    pkt_state = jnp.where(p_done_now, DONE, s.pkt_state)
    pkt_finish = jnp.where(p_done_now, time, s.pkt_finish)
    task_state = jnp.where(t_done_now, DONE, s.task_state)
    task_finish = jnp.where(t_done_now, time, s.task_finish)

    feeds = jnp.maximum(c.pkt_feeds_task, 0)
    task_got = s.task_got.at[feeds].add(
        (p_done_now & (c.pkt_feeds_task >= 0)).astype(jnp.int32))
    out_pkt = p_done_now & (c.pkt_feeds_task < 0)
    job_of = jnp.maximum(c.pkt_job, 0)
    job_out_done = s.job_out_done.at[job_of].add(out_pkt.astype(jnp.int32))
    newly_job_done = (job_out_done >= c.job_n_out) & \
        (s.job_out_done < c.job_n_out) & c.job_valid
    job_done_t = jnp.where(newly_job_done, time, s.job_done_t)
    vm_load = s.vm_load.at[vm_safe].add(-t_done_now.astype(jnp.int32))

    return s._replace(
        time=time, steps=s.steps + 1, stalled=stalled,
        job_out_done=job_out_done, job_done_t=job_done_t,
        task_state=task_state, task_rem=task_rem, task_got=task_got,
        task_finish=task_finish,
        pkt_state=pkt_state, pkt_rem=pkt_rem, pkt_finish=pkt_finish,
        vm_load=vm_load, host_energy=host_energy, host_busy=host_busy,
        switch_energy=switch_energy, job_downtime=job_downtime)


def ref_simulator(meta):
    """The pre-PR loop: ``_finished`` evaluated in cond AND body."""
    meta = SimMeta.coerce(meta)

    def run(consts, pol):
        s0 = init_state_from_consts(consts, meta.n_switches)

        def cond(s):
            return ~_ref_finished(consts, meta, s)

        def body(s):
            new = _ref_step(consts, meta, pol, s)
            live = ~_ref_finished(consts, meta, s)
            return jax.tree_util.tree_map(
                lambda n, o: jnp.where(live, n, o), new, s)

        return jax.lax.while_loop(cond, body, s0)

    return run


# ---------------------------------------------------------------------------
# the scenario x policy x seed grid
# ---------------------------------------------------------------------------

# every registered scenario, at reduced workload size: the REFERENCE
# kernel is O(n_packets) per event per replica, so the 36-policy grid only
# fits the test budget on small instances (the structures — topology
# family, workload shape, failure traces — are the registered ones; the
# slow-marked test below runs the full-size xl fabric)
NO_FAILURE_SCENARIOS = [
    ("paper-fabric", dict(split=1)),
    ("fat-tree", dict(n_jobs=4)),
    ("al-fares-fat-tree", dict(n_each=1, split=1, k_max=4)),
    ("leaf-spine", dict(n_jobs=4)),
    ("canonical-tree", dict(n_jobs=4)),
    ("leaf-spine-xl", dict(n_spine=2, n_leaf=2, hosts_per_leaf=2, n_jobs=4,
                           max_scale=1.5)),
    # the streaming scenario's FINITE arrival preview (DESIGN.md §11) is an
    # ordinary workload, so it belongs in the bit-identity grid too
    ("leaf-spine-stream", dict(n_spine=2, n_leaf=2, hosts_per_leaf=2,
                               horizon=160.0, max_jobs=4)),
]
FAILURE_SCENARIOS = [
    ("paper-fabric-failures", dict(split=1)),
    ("leaf-spine-failures", dict(n_jobs=4)),
]
# ctrl scenarios enter the bit-identity suite with their CtrlPlaneConfig
# STRIPPED: has_ctrl=False must trace the exact pre-control-plane program
# (DESIGN.md §10) — the on-behavior is covered by tests/test_ctrlplane.py
CTRL_SCENARIOS = [
    ("paper-fabric-ctrl", dict(split=1)),
    ("leaf-spine-ctrl", dict(n_jobs=4)),
]
# chaos scenarios enter with degradation, ctrl AND spec_slots STRIPPED:
# the §13 off switch must trace the exact pre-chaos program (what remains
# is a plain failures / plain scenario the reference kernel handles) —
# the on-behavior is covered by tests/test_chaos.py
CHAOS_SCENARIOS = [
    ("paper-fabric-chaos", dict(split=1)),
    ("leaf-spine-chaos", dict(n_jobs=4)),
]


def policy_grid(seeds=(0, 1, 2)):
    """All placement x routing x recovery branches; job-selection, traffic
    and concurrency cycle through their values across the combos."""
    jobsels = [JOBSEL_FCFS, JOBSEL_SJF, JOBSEL_PRIORITY]
    traffics = [TRAFFIC_FAIRSHARE, TRAFFIC_WATERFILL]
    concs = [1, 2, 1_000_000]
    pols = []
    for seed in seeds:
        for i, (p, r, rec) in enumerate(itertools.product(
                (PLACE_LEAST_USED, PLACE_ROUND_ROBIN, PLACE_RANDOM),
                (ROUTE_SDN, ROUTE_LEGACY),
                (RECOVERY_RESTART, RECOVERY_RESUME))):
            pols.append(PolicyConfig(
                placement=p, routing=r, recovery=rec,
                job_selection=jobsels[i % 3], traffic=traffics[i % 2],
                job_concurrency=concs[i % 3], seed=seed))
    return pols


def _run_grid(scenarios, strip_ctrl=False, strip_chaos=False):
    setups = [get_scenario(name, **kw).build() for name, kw in scenarios]
    if strip_ctrl:
        setups = [dataclasses.replace(s, ctrl=None) for s in setups]
    if strip_chaos:
        setups = [dataclasses.replace(s, degradation=None, ctrl=None,
                                      spec_slots=0) for s in setups]
    consts, meta = pack_setups(setups)
    pols = {k: jnp.asarray(v) for k, v in policy_arrays(policy_grid()).items()}

    ref_run = ref_simulator(meta)
    ref_grid = jax.jit(lambda c, p: jax.vmap(
        lambda ci: jax.vmap(lambda pi: ref_run(ci, pi))(p))(c))
    ref_states = jax.block_until_ready(ref_grid(consts, pols))
    new_states = jax.block_until_ready(
        runners.get_runner(meta, "grid")(consts, pols))
    return ref_states, new_states, [n for n, _ in scenarios]


def test_all_scenarios_registered():
    """The grids below must cover every registered scenario."""
    covered = {n for n, _ in
               NO_FAILURE_SCENARIOS + FAILURE_SCENARIOS + CTRL_SCENARIOS
               + CHAOS_SCENARIOS}
    assert covered == set(list_scenarios())


def test_grid_bit_identity_no_failures():
    ref_states, new_states, names = _run_grid(NO_FAILURE_SCENARIOS)
    for si, name in enumerate(names):
        ref = jax.tree_util.tree_map(lambda a: a[si], ref_states)
        new = jax.tree_util.tree_map(lambda a: a[si], new_states)
        assert_states_equal(ref, new, name)


def test_grid_bit_identity_with_failures():
    ref_states, new_states, names = _run_grid(FAILURE_SCENARIOS)
    for si, name in enumerate(names):
        ref = jax.tree_util.tree_map(lambda a: a[si], ref_states)
        new = jax.tree_util.tree_map(lambda a: a[si], new_states)
        assert_states_equal(ref, new, name)


def test_grid_bit_identity_ctrl_stripped():
    """The §10 off switch: the ctrl scenarios with their CtrlPlaneConfig
    removed must be BITWISE the pre-control-plane engine across the whole
    policy x seed grid — every control-plane path sits behind trace-time
    ``meta.has_ctrl`` branches, so has_ctrl=False is the identical
    program, not a dynamically-disabled one."""
    ref_states, new_states, names = _run_grid(CTRL_SCENARIOS,
                                              strip_ctrl=True)
    for si, name in enumerate(names):
        ref = jax.tree_util.tree_map(lambda a: a[si], ref_states)
        new = jax.tree_util.tree_map(lambda a: a[si], new_states)
        assert_states_equal(ref, new, name)


def test_grid_bit_identity_chaos_stripped():
    """The §13 off switch: the chaos scenarios with degradation, ctrl and
    clone capacity removed must be BITWISE the pre-chaos engine across the
    whole policy x seed grid — gray failures, speculation and failover all
    sit behind trace-time ``meta`` switches, so off is the identical
    program, not a dynamically-disabled one."""
    ref_states, new_states, names = _run_grid(CHAOS_SCENARIOS,
                                              strip_chaos=True)
    for si, name in enumerate(names):
        ref = jax.tree_util.tree_map(lambda a: a[si], ref_states)
        new = jax.tree_util.tree_map(lambda a: a[si], new_states)
        assert_states_equal(ref, new, name)


def test_single_run_bit_identity_unpacked():
    """The unpacked single-scenario path (no pad slots) also matches."""
    setup = get_scenario("leaf-spine").build()
    consts, meta = make_consts(setup)
    for pol_cfg in (PolicyConfig(job_concurrency=2),
                    PolicyConfig(routing=ROUTE_LEGACY,
                                 placement=PLACE_ROUND_ROBIN, seed=3)):
        pol = {k: jnp.asarray(v)
               for k, v in pol_cfg.as_arrays().items()}
        ref = jax.block_until_ready(
            jax.jit(ref_simulator(meta))(consts, pol))
        new = jax.block_until_ready(
            runners.get_runner(meta, "single")(consts, pol))
        assert_states_equal(ref, new, f"leaf-spine/{pol_cfg!r}")


@pytest.mark.slow
def test_full_size_xl_bit_identity():
    """Full leaf-spine-xl (128 hosts, >=1k tasks, >=4k packets): the
    reference kernel needs minutes here — slow-marked, one policy."""
    setup = get_scenario("leaf-spine-xl").build()
    consts, meta = make_consts(setup)
    pol = {k: jnp.asarray(v)
           for k, v in PolicyConfig(job_concurrency=4).as_arrays().items()}
    ref = jax.block_until_ready(jax.jit(ref_simulator(meta))(consts, pol))
    new = jax.block_until_ready(
        runners.get_runner(meta, "single")(consts, pol))
    assert_states_equal(ref, new, "leaf-spine-xl")
