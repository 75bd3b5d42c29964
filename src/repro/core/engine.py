"""The discrete-event engine as a single ``lax.while_loop``.

CloudSim's event heap disappears: between events every rate (channel
bandwidth, VM MIPS share, power draw) is piecewise constant, so the next
event time is an analytic ``min`` over fixed-shape state tensors (paper
Eq. 4 generalized to packet finishes, task finishes and job releases).
One while-loop iteration = one event:

  failure/recovery transitions (DESIGN.md §7, traced only when a schedule
  has a finite instant) -> admission -> placement -> task activation ->
  packet activation (routed) -> rates -> dt = earliest horizon ->
  energy += power*dt -> advance -> completions

The step interior is (near-)fully data-parallel (DESIGN.md §8): admission
ranks released jobs against the concurrency budget in one stable sort,
placement resolves a whole batch of tasks by rank-plus-counter arithmetic
over the live-VM prefix-sum remap (with a compacted scan only for the
load-feedback least-used policy), packet activation iterates only the
ready set (the legacy hash route needs no feedback and vectorizes
entirely), and the per-step network tensors — route links, channel
counts, effective link bandwidth — are computed once and threaded through
rates and energy.  Sequential tie-break order is preserved everywhere, so
the kernel is bit-identical to the scalar event loop it replaced
(tests/test_engine_equiv.py).

Everything is vmap-safe: ``simulate_batch`` sweeps policy/seed vectors as one
tensor program (the beyond-paper capability — see DESIGN.md §2).

The static side of a run is described by a typed, hashable ``SimMeta``
(DESIGN.md §6); ``simulate``/``simulate_batch``/``simulate_scenarios`` are
kept as thin deprecated shims over the unified ``repro.api`` front door
(``Experiment`` + the compiled-runner cache).
"""
from __future__ import annotations

from functools import partial
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import fairshare
from .ctrlplane import no_ctrl
from .failures import no_degradation, no_failures
from .mapreduce import ACTIVE, DONE, INSTALLING, SimSetup, VOID, WAITING
from .energy import host_power, switch_power
from .policies import (INSTALL_PROACTIVE, JOBSEL_PRIORITY, JOBSEL_SJF,
                       MIG_CONGESTION, PLACE_RANDOM, PLACE_ROUND_ROBIN,
                       RECOVERY_RESTART, SPEC_ON, as_policy_arrays)
from .routing import (ROUTE_SDN, flow_hash_u32, legacy_route_choice,
                      node_pair_hops, route_candidates, route_ends,
                      route_links, sdn_route_choice)
from .simmeta import SimMeta

_INF = jnp.float32(jnp.inf)


def static_policy_value(x):
    """Python int value of a policy field when it is host-static (a plain
    int / numpy scalar), else ``None``.

    Fleet cohorts group lanes by branch-selecting policy fields (routing,
    traffic, placement) and pass them as Python ints, so the engine can
    specialize the dispatch at trace time — under ``vmap`` a ``lax.cond``
    with a batched predicate lowers to a select that EXECUTES both
    branches, which is exactly the batch-wall pathology the fleet path
    exists to avoid (DESIGN.md §9).  Traced fields keep the vmap-safe
    dynamic dispatch unchanged."""
    if isinstance(x, (bool, int, np.integer)):
        return int(x)
    if isinstance(x, np.ndarray) and x.ndim == 0:
        return int(x)
    return None


def job_valid_mask(job_n_out):
    """A job slot is live iff it expects output packets — the ONE definition
    of job validity, shared by make_consts and the packed-sweep builder."""
    return job_n_out > 0


def task_rank_in_job_np(task_job) -> np.ndarray:
    """Host-side: position of each task among the tasks sharing its job id,
    in task-index order (pad tasks form their own ``-1`` group).  Static
    per setup — shared by make_consts and the packed-sweep builder."""
    tj = np.asarray(task_job, np.int64)
    order = np.argsort(tj, kind="stable")
    g = tj[order]
    n = g.shape[0]
    starts = np.r_[0, np.flatnonzero(g[1:] != g[:-1]) + 1]
    sizes = np.diff(np.r_[starts, n])
    out = np.empty(n, np.int32)
    out[order] = (np.arange(n) - np.repeat(starts, sizes)).astype(np.int32)
    return out


def job_n_tasks_np(task_job, task_valid, n_jobs: int) -> np.ndarray:
    """Host-side: valid-task count per job (static per setup)."""
    tj = np.asarray(task_job, np.int64)
    tv = np.asarray(task_valid, bool)
    return np.bincount(tj[tv & (tj >= 0)],
                       minlength=n_jobs).astype(np.int32)[:n_jobs]


class EngineConsts(NamedTuple):
    """Static (replica-shared) tensors, baked from SimSetup."""

    # routing (``routing.RouteTable``): candidates per pair of attachment
    # nodes; a node pair's route is uplink + attachment route + downlink
    routes: jnp.ndarray      # i32 [n_att, n_att, K, H]
    n_cand: jnp.ndarray      # i32 [n_att, n_att]
    # per node (attachment index, link to it, link from it); -1 = none
    node_route: jnp.ndarray  # i32 [n_nodes, 3]
    link_bw: jnp.ndarray     # [n_links]
    link_src: jnp.ndarray
    link_dst: jnp.ndarray
    # cluster
    vm_host: jnp.ndarray
    vm_total_mips: jnp.ndarray
    vm_core_mips: jnp.ndarray
    host_total_mips: jnp.ndarray
    # jobs / tasks / packets (see mapreduce.py)
    job_release: jnp.ndarray
    job_total_mi: jnp.ndarray
    job_priority: jnp.ndarray
    job_n_out: jnp.ndarray
    job_valid: jnp.ndarray
    task_job: jnp.ndarray
    task_kind: jnp.ndarray
    task_mi: jnp.ndarray
    task_need: jnp.ndarray
    task_valid: jnp.ndarray
    # position of each task among its job's tasks (index order) and each
    # job's valid-task count: the batched placement pass turns admission
    # rank + these into placement positions by pure arithmetic — no
    # per-step sort over the task axis (DESIGN.md §8)
    task_rank_in_job: jnp.ndarray  # int32 [n_tasks]
    job_n_tasks: jnp.ndarray       # int32 [n_jobs]
    pkt_job: jnp.ndarray
    pkt_phase: jnp.ndarray
    pkt_bits: jnp.ndarray
    pkt_gate_task: jnp.ndarray
    pkt_feeds_task: jnp.ndarray
    pkt_src_task: jnp.ndarray
    pkt_dst_task: jnp.ndarray
    pkt_valid: jnp.ndarray
    # scalars (static python ints/floats hidden in jnp for pytree friendliness)
    n_hosts: jnp.ndarray
    n_switches: jnp.ndarray
    storage_node: jnp.ndarray
    # live VM count — may be < len(vm_host) when consts are padded to a
    # common shape for a multi-scenario sweep (DESIGN.md §5); placement
    # must never pick a pad VM slot.
    n_vms: jnp.ndarray
    # failure schedule (DESIGN.md §7): outage window [fail_t, recover_t)
    # per host / per directed link; inf = never.  Just more piecewise-
    # constant rate breakpoints for the analytic dt min.
    host_fail_t: jnp.ndarray     # f32 [n_hosts]
    host_recover_t: jnp.ndarray  # f32 [n_hosts]
    link_fail_t: jnp.ndarray     # f32 [n_links]
    link_recover_t: jnp.ndarray  # f32 [n_links]
    # the same instants concatenated ([2*n_hosts + 2*n_links], inf=never):
    # the dt horizon mins over ONE tensor per step (DESIGN.md §8)
    fail_breaks: jnp.ndarray
    # gray-failure degradation schedule (DESIGN.md §13): a host runs at
    # host_deg_factor x MIPS on [host_slow_t, host_restore_t), a directed
    # link at link_deg_factor x bandwidth on its window — piecewise-
    # constant multipliers joining the same analytic dt min as the outage
    # tensors above.  inf slow_t / factor 1.0 = never.
    host_slow_t: jnp.ndarray     # f32 [n_hosts]
    host_restore_t: jnp.ndarray  # f32 [n_hosts]
    host_deg_factor: jnp.ndarray # f32 [n_hosts]
    link_slow_t: jnp.ndarray     # f32 [n_links]
    link_restore_t: jnp.ndarray  # f32 [n_links]
    link_deg_factor: jnp.ndarray # f32 [n_links]
    # live-window slow/restore instants concatenated (inert windows masked
    # to inf) — one masked min per step, mirroring fail_breaks
    deg_breaks: jnp.ndarray      # f32 [2*n_hosts + 2*n_links]
    # control plane (DESIGN.md §10): scalar resource parameters — the
    # identity values (0 latency, inf rate, inf threshold) when the replica
    # carries no CtrlPlaneConfig, so a packed sweep can mix configs.
    # ctrl_on gates the install/pre-pin paths per replica: an identity-
    # config lane in a mixed batch must bypass the controller entirely
    # (zero counters), not merely pay zero latency for it
    ctrl_on: jnp.ndarray        # bool []: this replica's config is live
    ctrl_latency: jnp.ndarray   # f32 []: flow-mod propagation latency (s)
    ctrl_rate: jnp.ndarray      # f32 []: controller rule installs per second
    mig_threshold: jnp.ndarray  # f32 []: aggregate route-hop migration trigger
    mig_cost: jnp.ndarray       # f32 []: compute pause per migration (s)
    mig_cooldown: jnp.ndarray   # f32 []: min quiet time between migrations
    mig_limit: jnp.ndarray      # i32 []: total migration budget per run
    # candidate hop count per attachment pair — the migration policy's
    # distance estimate (``routing.node_pair_hops``); 0 on the diagonal,
    # UNREACHABLE_HOPS where no route exists
    pair_hops: jnp.ndarray      # i32 [n_att, n_att]
    # controller failover (DESIGN.md §13): the primary controller is down
    # on [ctrl_fail_t, ctrl_recover_t); rule requests inside the first
    # ctrl_failover_delay seconds of the outage park until the backup's
    # leader election completes, then the backup serves at its own
    # rate/latency.  inf fail_t = never (the scalars are inert).
    ctrl_fail_t: jnp.ndarray         # f32 []
    ctrl_recover_t: jnp.ndarray      # f32 []
    ctrl_failover_delay: jnp.ndarray # f32 []
    ctrl_backup_rate: jnp.ndarray    # f32 []
    ctrl_backup_latency: jnp.ndarray # f32 []


class SimState(NamedTuple):
    time: jnp.ndarray
    steps: jnp.ndarray
    stalled: jnp.ndarray
    place_counter: jnp.ndarray
    # jobs
    job_admitted: jnp.ndarray
    job_admit_t: jnp.ndarray
    job_out_done: jnp.ndarray
    job_done_t: jnp.ndarray
    # tasks
    task_state: jnp.ndarray
    task_rem: jnp.ndarray
    task_got: jnp.ndarray
    task_vm: jnp.ndarray
    task_start: jnp.ndarray
    task_finish: jnp.ndarray
    # packets
    pkt_state: jnp.ndarray
    pkt_rem: jnp.ndarray
    pkt_pair: jnp.ndarray
    pkt_cand: jnp.ndarray
    # [n_packets, H] links of the chosen route, composed once when the
    # route is chosen; meaningful while pkt_cand >= 0
    pkt_links: jnp.ndarray
    pkt_start: jnp.ndarray
    pkt_finish: jnp.ndarray
    # vms / energy
    vm_load: jnp.ndarray
    host_energy: jnp.ndarray
    host_busy: jnp.ndarray
    switch_energy: jnp.ndarray
    # failure & recovery (DESIGN.md §7)
    host_dead: jnp.ndarray      # bool [n_hosts]: inside outage window
    link_dead: jnp.ndarray      # bool [n_links]
    task_restarts: jnp.ndarray  # int32 [n_tasks]: YARN re-executions
    pkt_reroutes: jnp.ndarray   # int32 [n_packets]: failure-driven reverts
    job_downtime: jnp.ndarray   # f32 [n_jobs]: admitted-but-zero-progress s
    # control plane (DESIGN.md §10).  All of it rides in the carry so the
    # flow-table / controller-queue evolution stays inside the one
    # while_loop; with has_ctrl=False every field passes through untouched.
    vm_host: jnp.ndarray        # i32 [n_vms]: LIVE placement (migration
    #                             re-homes VMs; == c.vm_host when static)
    ftab_pair: jnp.ndarray      # i32 [n_switches, ctrl_slots]: cached pair
    #                             per flow-table slot (-1 = empty)
    ftab_ready: jnp.ndarray     # f32 [n_switches, ctrl_slots]: instant the
    #                             slot's rule finishes installing
    ftab_stamp: jnp.ndarray     # i32 [n_switches, ctrl_slots]: LRU stamp
    ctrl_busy: jnp.ndarray      # f32 []: controller next-free instant
    ctrl_stamp: jnp.ndarray     # i32 []: monotone LRU counter
    ctrl_installs: jnp.ndarray  # i32 []: rule installs requested
    ctrl_evictions: jnp.ndarray # i32 []: rules LRU-displaced (or uncached)
    ctrl_reinstalls: jnp.ndarray  # i32 []: installs for churn-evicted flows
    ctrl_queue_wait: jnp.ndarray  # f32 []: summed wait in the ctrl queue
    pkt_ready_t: jnp.ndarray    # f32 [n_packets]: INSTALLING wake instant
    pkt_install_wait: jnp.ndarray  # f32 [n_packets]: summed install stall
    vm_mig_until: jnp.ndarray   # f32 [n_vms]: migration compute-pause end
    vm_migrations: jnp.ndarray  # i32 [n_vms]: re-homings taken
    # gray failures, speculation & failover (DESIGN.md §13).  The spec_*
    # tensors are the statically pre-allocated per-job clone slots
    # ([n_jobs * SimMeta.spec_slots], zero-length when speculation is
    # structurally off); everything passes through untouched when the
    # corresponding meta switch is off.
    degraded_time: jnp.ndarray  # f32 []: time with any live deg window
    spec_of: jnp.ndarray        # i32 [S]: cloned original task (-1 free)
    spec_vm: jnp.ndarray        # i32 [S]: VM the clone runs on
    spec_rem: jnp.ndarray       # f32 [S]: clone's remaining MI
    spec_start: jnp.ndarray     # f32 [S]: clone launch instant
    task_cloned: jnp.ndarray    # bool [n_tasks]: ever speculated (once)
    spec_launches: jnp.ndarray  # i32 []: clones launched
    spec_wins: jnp.ndarray      # i32 []: clones that beat their original
    spec_wasted: jnp.ndarray    # f32 []: losing-copy runtime (VM-seconds)
    ctrl_failovers: jnp.ndarray # i32 []: primary-controller outages hit
    ctrl_failover_park: jnp.ndarray  # f32 []: install delay added by the
    #                             leader-election gap (summed)


def default_max_steps(setup: SimSetup) -> int:
    """Step cap: the no-failure event bound, plus — when a failure schedule
    is present — one full re-execution budget per fail/recover instant
    (each failure can revert every in-flight task/packet at most once).
    The failure-mode cap is quantized to the next power of two so that
    schedules differing only in outage COUNT share a ``SimMeta`` and hit
    the compiled-runner cache (DESIGN.md §6)."""
    base = 4 * (setup.n_packets + setup.n_tasks) + 4 * setup.n_jobs + 64
    sched = setup.failures
    steps = base
    quantize = False
    if sched is not None and sched.any_failures:
        steps = base * (1 + sched.n_events) + 2 * sched.n_events
        quantize = True
    deg = setup.degradation
    if deg is not None and deg.any_degradation:
        # a degradation window reverts nothing — it only adds its
        # slow/restore breakpoints as extra event steps (DESIGN.md §13)
        steps = steps + 4 * deg.n_events + 8
        quantize = True
    if setup.spec_slots > 0:
        # each task is cloned at most once: one clone finish breakpoint
        # plus one cleanup-visible cancellation step per task bounds the
        # speculation event budget (DESIGN.md §13)
        steps = steps + 2 * setup.n_tasks
        quantize = True
    cfg = setup.ctrl
    if cfg is not None and cfg.any_ctrl:
        # reactive installation splits each packet activation into a
        # park + wake pair (one extra breakpoint per packet), and every
        # migration can revert + re-run every in-flight packet once
        # (DESIGN.md §10) — same quantization rationale as failures
        steps = 2 * steps + cfg.mig_limit * (3 * setup.n_packets + 4)
        quantize = True
    if quantize:
        return 1 << (steps - 1).bit_length()
    return steps


def make_consts(setup: SimSetup) -> tuple[EngineConsts, SimMeta]:
    rt, cl = setup.route_table, setup.cluster
    sched = setup.failures
    if sched is None:
        sched = no_failures(cl.topo.n_hosts, cl.topo.n_links)
    else:
        sched.validate(cl.topo.n_hosts, cl.topo.n_links)
    deg = setup.degradation
    if deg is None:
        deg = no_degradation(cl.topo.n_hosts, cl.topo.n_links)
    else:
        deg.validate(cl.topo.n_hosts, cl.topo.n_links)
    cfg = (setup.ctrl or no_ctrl()).validate()
    consts = EngineConsts(
        **{k: jnp.asarray(v) for k, v in rt.device_arrays().items()},
        link_bw=jnp.asarray(cl.topo.link_bw),
        link_src=jnp.asarray(cl.topo.link_src),
        link_dst=jnp.asarray(cl.topo.link_dst),
        vm_host=jnp.asarray(cl.vm_host),
        vm_total_mips=jnp.asarray(cl.vm_total_mips),
        vm_core_mips=jnp.asarray(cl.vm_core_mips),
        host_total_mips=jnp.asarray(cl.host_total_mips),
        job_release=jnp.asarray(setup.job_release),
        job_total_mi=jnp.asarray(setup.job_total_mi),
        job_priority=jnp.asarray(setup.job_priority),
        job_n_out=jnp.asarray(setup.job_n_out),
        job_valid=jnp.asarray(job_valid_mask(setup.job_n_out)),
        task_job=jnp.asarray(setup.task_job),
        task_kind=jnp.asarray(setup.task_kind),
        task_mi=jnp.asarray(setup.task_mi),
        task_need=jnp.asarray(setup.task_need),
        task_valid=jnp.asarray(setup.task_valid),
        task_rank_in_job=jnp.asarray(task_rank_in_job_np(setup.task_job)),
        job_n_tasks=jnp.asarray(job_n_tasks_np(
            setup.task_job, setup.task_valid, setup.n_jobs)),
        pkt_job=jnp.asarray(setup.pkt_job),
        pkt_phase=jnp.asarray(setup.pkt_phase),
        pkt_bits=jnp.asarray(setup.pkt_bits),
        pkt_gate_task=jnp.asarray(setup.pkt_gate_task),
        pkt_feeds_task=jnp.asarray(setup.pkt_feeds_task),
        pkt_src_task=jnp.asarray(setup.pkt_src_task),
        pkt_dst_task=jnp.asarray(setup.pkt_dst_task),
        pkt_valid=jnp.asarray(setup.pkt_valid),
        n_hosts=jnp.asarray(cl.topo.n_hosts, jnp.int32),
        n_switches=jnp.asarray(cl.topo.n_switches, jnp.int32),
        storage_node=jnp.asarray(cl.storage_node, jnp.int32),
        n_vms=jnp.asarray(int(cl.vm_host.shape[0]), jnp.int32),
        host_fail_t=jnp.asarray(sched.host_fail_t, jnp.float32),
        host_recover_t=jnp.asarray(sched.host_recover_t, jnp.float32),
        link_fail_t=jnp.asarray(sched.link_fail_t, jnp.float32),
        link_recover_t=jnp.asarray(sched.link_recover_t, jnp.float32),
        fail_breaks=jnp.asarray(sched.instants(), jnp.float32),
        host_slow_t=jnp.asarray(deg.host_slow_t, jnp.float32),
        host_restore_t=jnp.asarray(deg.host_restore_t, jnp.float32),
        host_deg_factor=jnp.asarray(deg.host_factor, jnp.float32),
        link_slow_t=jnp.asarray(deg.link_slow_t, jnp.float32),
        link_restore_t=jnp.asarray(deg.link_restore_t, jnp.float32),
        link_deg_factor=jnp.asarray(deg.link_factor, jnp.float32),
        deg_breaks=jnp.asarray(deg.instants(), jnp.float32),
        ctrl_on=jnp.asarray(cfg.any_ctrl),
        ctrl_latency=jnp.asarray(cfg.install_latency, jnp.float32),
        ctrl_rate=jnp.asarray(cfg.ctrl_rate, jnp.float32),
        mig_threshold=jnp.asarray(cfg.mig_threshold, jnp.float32),
        mig_cost=jnp.asarray(cfg.mig_cost, jnp.float32),
        mig_cooldown=jnp.asarray(cfg.mig_cooldown, jnp.float32),
        mig_limit=jnp.asarray(cfg.mig_limit, jnp.int32),
        ctrl_fail_t=jnp.asarray(cfg.ctrl_fail_t, jnp.float32),
        ctrl_recover_t=jnp.asarray(cfg.ctrl_recover_t, jnp.float32),
        ctrl_failover_delay=jnp.asarray(cfg.failover_delay, jnp.float32),
        ctrl_backup_rate=jnp.asarray(cfg.backup_rate, jnp.float32),
        ctrl_backup_latency=jnp.asarray(cfg.backup_latency, jnp.float32),
    )
    meta = SimMeta(
        n_nodes=cl.topo.n_nodes,
        n_links=cl.topo.n_links,
        n_hosts=cl.topo.n_hosts,
        n_switches=cl.topo.n_switches,
        n_vms=int(cl.vm_host.shape[0]),
        intra_bw=cl.intra_bw,
        energy=cl.energy,
        max_steps=default_max_steps(setup),
        has_failures=sched.any_failures,
        has_ctrl=cfg.any_ctrl,
        ctrl_slots=cfg.table_slots if cfg.any_ctrl else 0,
        has_degradation=deg.any_degradation,
        spec_slots=setup.spec_slots,
    )
    return consts, meta


def init_state_from_consts(c: EngineConsts, n_switches: int,
                           ctrl_slots: int = 0,
                           spec_slots: int = 0) -> SimState:
    """t=0 state derived purely from (possibly padded) const tensors.

    ``n_switches`` is the STATIC switch-tensor length (padded max in a
    multi-scenario sweep) — it cannot be read off any consts array, every
    other shape can.  Pad job/task/packet slots start VOID/zero so they are
    inert for the whole run (DESIGN.md §5).  ``ctrl_slots`` is the static
    per-switch flow-table width (``SimMeta.ctrl_slots``) — 0 gives the
    flow-table tensors a zero-length slot axis (DESIGN.md §10).
    ``spec_slots`` is the static per-job speculative-clone slot count
    (``SimMeta.spec_slots``) — 0 gives the clone tensors a zero-length
    axis (DESIGN.md §13).
    """
    n_j = c.job_release.shape[0]
    n_t = c.task_job.shape[0]
    n_p = c.pkt_job.shape[0]
    n_s = n_j * spec_slots
    f = jnp.float32
    return SimState(
        time=f(0.0), steps=jnp.int32(0), stalled=jnp.asarray(False),
        place_counter=jnp.int32(0),
        job_admitted=jnp.zeros(n_j, bool),
        job_admit_t=jnp.full(n_j, jnp.nan, f),
        job_out_done=jnp.zeros(n_j, jnp.int32),
        job_done_t=jnp.full(n_j, jnp.nan, f),
        task_state=jnp.where(c.task_valid, WAITING, VOID).astype(jnp.int32),
        task_rem=c.task_mi.astype(f),
        task_got=jnp.zeros(n_t, jnp.int32),
        task_vm=jnp.full(n_t, -1, jnp.int32),
        task_start=jnp.full(n_t, jnp.nan, f),
        task_finish=jnp.full(n_t, jnp.nan, f),
        pkt_state=jnp.where(c.pkt_valid, WAITING, VOID).astype(jnp.int32),
        pkt_rem=c.pkt_bits.astype(f),
        pkt_pair=jnp.full(n_p, -1, jnp.int32),
        pkt_cand=jnp.full(n_p, -1, jnp.int32),
        pkt_links=jnp.full((n_p, c.routes.shape[-1]), -1, jnp.int32),
        pkt_start=jnp.full(n_p, jnp.nan, f),
        pkt_finish=jnp.full(n_p, jnp.nan, f),
        vm_load=jnp.zeros(c.vm_host.shape[0], jnp.int32),
        host_energy=jnp.zeros(c.host_total_mips.shape[0], f),
        host_busy=jnp.zeros(c.host_total_mips.shape[0], f),
        switch_energy=jnp.zeros(n_switches, f),
        host_dead=jnp.zeros(c.host_fail_t.shape[0], bool),
        link_dead=jnp.zeros(c.link_fail_t.shape[0], bool),
        task_restarts=jnp.zeros(n_t, jnp.int32),
        pkt_reroutes=jnp.zeros(n_p, jnp.int32),
        job_downtime=jnp.zeros(n_j, f),
        vm_host=c.vm_host.astype(jnp.int32),
        ftab_pair=jnp.full((n_switches, ctrl_slots), -1, jnp.int32),
        ftab_ready=jnp.zeros((n_switches, ctrl_slots), f),
        ftab_stamp=jnp.zeros((n_switches, ctrl_slots), jnp.int32),
        ctrl_busy=f(0.0),
        ctrl_stamp=jnp.int32(0),
        ctrl_installs=jnp.int32(0),
        ctrl_evictions=jnp.int32(0),
        ctrl_reinstalls=jnp.int32(0),
        ctrl_queue_wait=f(0.0),
        pkt_ready_t=jnp.full(n_p, jnp.inf, f),
        pkt_install_wait=jnp.zeros(n_p, f),
        vm_mig_until=jnp.zeros(c.vm_host.shape[0], f),
        vm_migrations=jnp.zeros(c.vm_host.shape[0], jnp.int32),
        degraded_time=f(0.0),
        spec_of=jnp.full(n_s, -1, jnp.int32),
        spec_vm=jnp.full(n_s, -1, jnp.int32),
        spec_rem=jnp.zeros(n_s, f),
        spec_start=jnp.zeros(n_s, f),
        task_cloned=jnp.zeros(n_t, bool),
        spec_launches=jnp.int32(0),
        spec_wins=jnp.int32(0),
        spec_wasted=f(0.0),
        ctrl_failovers=jnp.int32(0),
        ctrl_failover_park=f(0.0),
    )


def init_state(setup: SimSetup) -> SimState:
    consts, meta = make_consts(setup)
    return init_state_from_consts(consts, meta.n_switches, meta.ctrl_slots,
                                  meta.spec_slots)


# ---------------------------------------------------------------------------
# step phases
# ---------------------------------------------------------------------------


def _effective_link_bw(c: EngineConsts, meta, s: SimState) -> jnp.ndarray:
    """Per-link capacity with gray-degradation windows applied (DESIGN.md
    §13) and dead links at 0 (DESIGN.md §7).  Without degradation and
    failures this IS ``c.link_bw`` — the off-switch trace is unchanged.
    SDN's bottleneck route choice reads this tensor, so it steers around
    degraded links exactly like dead ones; the legacy static hash is
    degradation-blind."""
    bw = c.link_bw
    if meta.has_degradation:
        slow = (c.link_slow_t <= s.time) & (s.time < c.link_restore_t)
        bw = jnp.where(slow, bw * c.link_deg_factor, bw)
    if meta.has_failures:
        bw = jnp.where(s.link_dead, 0.0, bw)
    return bw


def _host_deg_factor(c: EngineConsts, s: SimState) -> jnp.ndarray:
    """Per-host MIPS multiplier from the gray-degradation windows
    (DESIGN.md §13): ``host_deg_factor`` inside ``[slow_t, restore_t)``,
    1.0 outside.  Only traced when ``meta.has_degradation``."""
    slow = (c.host_slow_t <= s.time) & (s.time < c.host_restore_t)
    return jnp.where(slow, c.host_deg_factor, jnp.float32(1.0))


def _effective_host_mips(c: EngineConsts, meta, s: SimState) -> jnp.ndarray:
    """Per-host MIPS capacity with gray-degradation windows applied
    (DESIGN.md §13) — the compute-side twin of ``_effective_link_bw``.
    Feeds the energy utilization denominator: a saturated degraded host
    draws full power for less work (the gray-failure energy story).
    Without degradation this IS ``c.host_total_mips``."""
    if meta.has_degradation:
        return c.host_total_mips * _host_deg_factor(c, s)
    return c.host_total_mips


def _vm_host(c: EngineConsts, meta, s: SimState) -> jnp.ndarray:
    """Effective VM -> host placement: the MUTABLE ``s.vm_host`` when the
    control plane is on (migration re-homes VMs — DESIGN.md §10), else the
    static ``c.vm_host`` — the no-ctrl trace is unchanged."""
    if meta.has_ctrl:
        return s.vm_host
    return c.vm_host


def _refresh_failures(c: EngineConsts, s: SimState):
    """Recompute the dead masks from the schedule at ``s.time`` and store
    them; -> ``(s, new_h, new_l)``, the hosts and links that died since
    the masks were last refreshed (DESIGN.md §7)."""
    t = s.time
    host_dead = (c.host_fail_t <= t) & (t < c.host_recover_t)
    link_dead = (c.link_fail_t <= t) & (t < c.link_recover_t)
    new_h = host_dead & ~s.host_dead
    new_l = link_dead & ~s.link_dead
    return (s._replace(host_dead=host_dead, link_dead=link_dead),
            new_h, new_l)


def _fail_transitions(c: EngineConsts, meta, pol, s: SimState, nc0,
                      new_h, new_l):
    """The one-shot transitions of the hosts ``new_h`` and links ``new_l``
    that just died; -> ``(s, nc)``.  With both masks all-false every hit
    mask is false and the channel-drop loop has zero trips, so the result
    is ``(s, nc0)`` bit for bit — which lets the fleet chunk run it for
    every lane once any lane needs it (DESIGN.md §9)."""
    with jax.named_scope("fail_transitions"):
        restart = pol["recovery"] == RECOVERY_RESTART
        # packets first: endpoints must resolve against the ACTIVATION-time
        # placement, i.e. before any task unplaces below.
        n_hosts_pad = c.host_fail_t.shape[0]
        src_node, dst_node = _pkt_endpoints(c, meta, s)
        p_active = s.pkt_state == ACTIVE
        if meta.has_ctrl:
            # a routed packet is also one parked in INSTALLING or one the
            # proactive pass pre-pinned while WAITING (DESIGN.md §10) —
            # a dead link/endpoint invalidates those routes too
            routed = (p_active | (s.pkt_state == INSTALLING)
                      | ((s.pkt_state == WAITING) & (s.pkt_cand >= 0)))
        else:
            routed = p_active
        links = _route_links(s, routed)
        route_hit = routed & jnp.any(
            (links >= 0) & new_l[jnp.maximum(links, 0)], axis=-1)

        def _endpoint_died(node):
            return (node < c.n_hosts) & new_h[jnp.clip(node, 0,
                                                       n_hosts_pad - 1)]

        ep_hit = routed & (_endpoint_died(src_node)
                           | _endpoint_died(dst_node))
        hit_p = route_hit | ep_hit
        pkt_state = jnp.where(hit_p, WAITING, s.pkt_state)
        pkt_rem = jnp.where(ep_hit & restart, c.pkt_bits.astype(jnp.float32),
                            s.pkt_rem)
        pkt_pair = jnp.where(hit_p, -1, s.pkt_pair)
        pkt_cand = jnp.where(hit_p, -1, s.pkt_cand)
        pkt_reroutes = s.pkt_reroutes + hit_p.astype(jnp.int32)
        if meta.has_ctrl:
            # a reverted INSTALLING packet re-requests its rules later
            s = s._replace(pkt_ready_t=jnp.where(hit_p, jnp.inf,
                                                 s.pkt_ready_t))
            # only the packets that were ACTIVE hold channels to release
            hit_drop = hit_p & p_active
        else:
            hit_drop = hit_p

        # tasks on newly-dead hosts
        vm_safe = jnp.maximum(s.task_vm, 0)
        task_host = jnp.clip(_vm_host(c, meta, s)[vm_safe], 0,
                             n_hosts_pad - 1)
        hit_t = (c.task_valid & (s.task_vm >= 0) & new_h[task_host]
                 & ((s.task_state == ACTIVE) | (s.task_state == WAITING)))
        task_state = jnp.where(hit_t, WAITING, s.task_state)
        task_rem = jnp.where(hit_t & restart, c.task_mi.astype(jnp.float32),
                             s.task_rem)
        task_start = jnp.where(hit_t, jnp.nan, s.task_start)
        # one-hot contraction, not a scatter: under a per-lane cond (the
        # batched runners) this runs every step, and batched scatters
        # serialize per lane
        vm_iota = jnp.arange(s.vm_load.shape[0], dtype=jnp.int32)
        vm_load = s.vm_load - jnp.sum(
            (vm_safe[:, None] == vm_iota[None, :]) & hit_t[:, None],
            axis=0).astype(jnp.int32)
        task_vm = jnp.where(hit_t, -1, s.task_vm)
        task_restarts = s.task_restarts + hit_t.astype(jnp.int32)

        s = s._replace(
            pkt_state=pkt_state, pkt_rem=pkt_rem, pkt_pair=pkt_pair,
            pkt_cand=pkt_cand, pkt_reroutes=pkt_reroutes,
            task_state=task_state, task_rem=task_rem, task_start=task_start,
            task_vm=task_vm, vm_load=vm_load, task_restarts=task_restarts)
        # reverted packets left the active set: subtract exactly their
        # channel contributions via a compacted per-packet scan (loop
        # length = the revert count, zero on recovery-only steps).  The
        # carried nc is maintained exactly by activation/completion, so
        # this equals a from-scratch recount bit-for-bit — but a recount's
        # [n_p, H, n_links] one-hot runs every step under a per-lane cond
        # (DESIGN.md §9) and dominated the failure-grid fleet profile.
        n_p = hit_p.shape[0]
        pidx = jnp.arange(n_p, dtype=jnp.int32)
        liota = jnp.arange(meta.n_links, dtype=jnp.int32)

        def drop_one(k, carry):
            nc, cursor = carry
            i = jnp.min(jnp.where(hit_drop & (pidx > cursor), pidx, n_p))
            links_k = links[jnp.minimum(i, n_p - 1)]
            nc = nc - jnp.sum((links_k[:, None] == liota[None, :])
                              .astype(jnp.int32), axis=0)
            return nc, i

        nc, _ = jax.lax.fori_loop(0, jnp.sum(hit_drop.astype(jnp.int32)),
                                  drop_one, (nc0, jnp.int32(-1)))
    return s, nc


def _apply_failures(c: EngineConsts, meta, pol, s: SimState, cache,
                    fire=None):
    """Fire every fail/recover transition whose instant has been reached.

    Failure instants join the dt horizon (``_step``), so ``s.time`` lands
    exactly on each one; here — at the top of the next iteration — the dead
    masks are recomputed from the schedule and the DELTA vs the previous
    masks drives the one-shot transitions (DESIGN.md §7):

      * WAITING/ACTIVE tasks on a newly-dead host revert to WAITING and
        unplace (``task_vm=-1``) — YARN re-execution on heartbeat loss;
        under ``recovery=restart`` their progress is lost, under ``resume``
        (beyond-paper checkpointing) ``task_rem`` survives.
      * In-flight packets whose chosen route crosses a newly-dead link
        revert to WAITING for re-routing (bits already delivered survive:
        the stream resumes on the new route).
      * In-flight packets whose src/dst HOST newly died revert too — the
        connection died with the endpoint — and retransmit from scratch
        under ``restart``.

    DONE work is never reverted (completed outputs are durable — the SAN
    holds T3 results, map outputs are re-fetchable); recovery instants need
    no transition, the masks simply clear.

    The revert scans (``_fail_transitions``) only matter on the handful of
    steps where something newly died, so they sit behind a ``lax.cond`` on
    the death delta — recovery-only and steady-state steps just refresh
    the dead masks (``_refresh_failures``, DESIGN.md §8).  The serial
    runner's cond really branches; under the batched runners' vmap it
    lowers to a select.  The fleet chunk passes ``fire``, the predicate
    decided once for all its lanes outside its lane vmap, so its cond
    stays a cond (``make_fleet_chunk``, DESIGN.md §9).
    """
    s, new_h, new_l = _refresh_failures(c, s)
    if fire is None:
        fire = jnp.any(new_h) | jnp.any(new_l)
    s, nc = jax.lax.cond(
        fire,
        lambda a: _fail_transitions(c, meta, pol, *a, new_h, new_l),
        lambda a: a, (s, cache["nc"]))
    return s, {**cache, "nc": nc}


def _place_batch(c: EngineConsts, meta, pol, aux, s: SimState, mine, pos,
                 vm_live, n_live) -> SimState:
    """Place every task in ``mine`` preserving the sequential placement
    order.  ``pos`` is each mine-task's 0-based position in that order
    (garbage outside ``mine`` — masked here), computed by the caller with
    prefix-sum arithmetic so no per-step sort is needed (DESIGN.md §8).

    Round-robin and random placement need no load feedback, so their picks
    are pure rank-plus-counter / hash arithmetic against the k-th-live VM
    remap.  Least-used must see each earlier placement's load bump, so it
    runs a compacted scan over the tasks-to-place only (loop length = the
    live placement count, not the padded task axis).

    Nothing axis-wide happens outside the branch actually taken: the
    vectorized picks (and the live-VM remap they index) build inside
    ``place_vec``, and the least-used scan finds its k-th task by a
    per-trip masked argmax instead of a precomputed inverse-permutation
    scatter — under a vmapped cond this body runs EVERY step, and a
    batched scatter serializes one row per lane (DESIGN.md §9)."""
    counter0 = s.place_counter
    n_mine = jnp.sum(mine.astype(jnp.int32))
    mod = jnp.maximum(n_live, 1)

    def place_vec(_):
        # kth[k] = slot index of the k-th live VM (stable sort: live slots
        # first in ascending index order, so a ``% mod`` pick never lands
        # on a dead/pad slot) — same values the old prefix-sum scatter
        # produced
        kth = jnp.argsort(~vm_live)
        rr_pick = kth[(counter0 + pos) % mod]
        rnd_pick = kth[aux["task_hash"] % mod]
        vec_pick = jnp.where(pol["placement"] == PLACE_ROUND_ROBIN,
                             rr_pick, rnd_pick)
        task_vm = jnp.where(mine, vec_pick, s.task_vm)
        vm_load = s.vm_load.at[
            jnp.where(mine, vec_pick, meta.n_vms)].add(1, mode="drop")
        return vm_load, task_vm

    def place_scan(_):
        imax = jnp.iinfo(jnp.int32).max

        def place_one(k, carry):
            vm_load, task_vm = carry
            t = jnp.argmax(mine & (pos == k)).astype(jnp.int32)
            pick = jnp.argmin(jnp.where(vm_live, vm_load, imax)
                              ).astype(jnp.int32)
            return vm_load.at[pick].add(1), task_vm.at[t].set(pick)

        return jax.lax.fori_loop(0, n_mine, place_one,
                                 (s.vm_load, s.task_vm))

    # any placement id that is neither round-robin nor random falls to the
    # load-feedback scan — same fallback the scalar kernel had.  A
    # host-static placement id (fleet cohorts — DESIGN.md §9) picks the
    # branch at trace time so vmap never builds the unused one.
    placement_static = static_policy_value(pol["placement"])
    if placement_static is not None:
        branch = (place_scan if placement_static not in
                  (PLACE_ROUND_ROBIN, PLACE_RANDOM) else place_vec)
        vm_load, task_vm = branch(None)
    else:
        use_scan = ((pol["placement"] != PLACE_ROUND_ROBIN)
                    & (pol["placement"] != PLACE_RANDOM))
        vm_load, task_vm = jax.lax.cond(use_scan, place_scan, place_vec,
                                        None)
    return s._replace(vm_load=vm_load, task_vm=task_vm,
                      place_counter=counter0 + n_mine)


def _admit_and_place(c: EngineConsts, meta, pol, aux, s: SimState):
    """Admit released jobs (job-selection policy) while concurrency slots are
    free; place each admitted job's tasks onto VMs (placement policy).

    Both halves are batched (DESIGN.md §8).  Admission: one stable sort of
    the released jobs by the policy key (ties by job index, exactly the
    repeated-argmin order of the scalar loop) admits the top
    ``concurrency - running`` of them at once — each sequential admission
    raised ``running`` by one, so the budget IS a rank cutoff.  Placement:
    every newly-admitted job's tasks are placed in one ``_place_batch``
    whose order key is the admission rank.

    With failures enabled, placement only considers VMs on LIVE hosts (the
    ResourceManager's heartbeat view — DESIGN.md §7) and a second batch
    re-places unplaced tasks of already-admitted jobs (YARN re-execution
    after a host loss).

    Returns ``(s, placed, admit_now)``: ``placed`` is True iff any task
    placement changed this step — ``_step`` uses it to refresh the
    packet-endpoint cache only when needed; ``admit_now`` marks the jobs
    admitted THIS step (the proactive install pass pre-pins exactly their
    packets — DESIGN.md §10)."""
    # live VM count (c.n_vms) may be smaller than the padded tensor length
    # in a packed multi-scenario sweep — pad slots must never win placement.
    n_vms = c.n_vms
    vm_live = jnp.arange(meta.n_vms) < n_vms
    if meta.has_failures:
        vm_live = vm_live & ~s.host_dead[
            jnp.clip(_vm_host(c, meta, s), 0, c.host_fail_t.shape[0] - 1)]
    n_live = jnp.sum(vm_live.astype(jnp.int32))

    n_j = s.job_admitted.shape[0]
    released = (~s.job_admitted) & c.job_valid & (c.job_release <= s.time)
    running = jnp.sum((s.job_admitted & (s.job_out_done < c.job_n_out)
                       & c.job_valid).astype(jnp.int32))
    slots = jnp.maximum(pol["job_concurrency"].astype(jnp.int32) - running, 0)
    if meta.has_failures:
        # no live NodeManager, no admission (the RM has nowhere to place):
        # jobs wait for a host recovery breakpoint
        slots = jnp.where(n_live > 0, slots, 0)
    # job-selection key (smaller = better)
    key = jnp.where(
        pol["job_selection"] == JOBSEL_SJF, c.job_total_mi,
        jnp.where(pol["job_selection"] == JOBSEL_PRIORITY,
                  -c.job_priority, c.job_release))
    key = jnp.where(released, key, _INF)
    # rank = inverse of the stable sort permutation (argsort of argsort);
    # no job-axis scatter — this runs every step under vmap (DESIGN.md §9)
    ord_j = jnp.argsort(key)
    rank = jnp.argsort(ord_j).astype(jnp.int32)
    admit_now = released & (rank < slots)

    job_of_task = jnp.maximum(c.task_job, 0)
    any_admit = jnp.any(admit_now)

    def admit_place(s: SimState) -> SimState:
        # placement position of every admitted task by prefix-sum
        # arithmetic (admission-rank-major, task-index-minor —
        # DESIGN.md §8): offset each job's static task block by the task
        # counts of better-ranked admitted jobs, then add the task's
        # static rank within its job.
        mine = c.task_valid & admit_now[job_of_task]
        # rank-major task counts by GATHERING through the sort permutation
        # (cnt_by_rank[r] = task count of the rank-r job) — not a scatter
        cnt_by_rank = jnp.where(admit_now[ord_j], c.job_n_tasks[ord_j], 0)
        off_by_rank = jnp.cumsum(cnt_by_rank) - cnt_by_rank  # exclusive
        pos = off_by_rank[rank[job_of_task]] + c.task_rank_in_job
        return _place_batch(c, meta, pol, aux, s, mine, pos, vm_live,
                            n_live)

    s = jax.lax.cond(any_admit, admit_place, lambda s: s, s)
    s = s._replace(job_admitted=s.job_admitted | admit_now,
                   job_admit_t=jnp.where(admit_now, s.time, s.job_admit_t))
    placed = any_admit

    if meta.has_failures:
        # re-place tasks a host failure unplaced (jobs already admitted);
        # with no live VM they stay unplaced and wait for a recovery.
        orphaned = (c.task_valid & (s.task_vm < 0)
                    & (s.task_state == WAITING)
                    & s.job_admitted[job_of_task]
                    & (n_live > 0))
        s = jax.lax.cond(
            jnp.any(orphaned),
            lambda s: _place_batch(
                c, meta, pol, aux, s, orphaned,
                jnp.cumsum(orphaned.astype(jnp.int32)) - 1, vm_live,
                n_live),
            lambda s: s, s)
        placed = placed | jnp.any(orphaned)
    return s, placed, admit_now


def _route_links(s: SimState, mask: jnp.ndarray) -> jnp.ndarray:
    """[N_P, H] link ids of each packet's chosen route (-1 where masked)."""
    return jnp.where(mask[:, None], s.pkt_links, -1)


NODE_OFFSET = 1 << 20  # pkt_src/dst_task >= NODE_OFFSET encodes a direct
                       # node id (flow-level frontend, core.flows)


def _pkt_endpoints(c: EngineConsts, meta, s: SimState):
    """Resolve src/dst node of every packet from current task placement
    (the LIVE placement under migration — ``_vm_host``, DESIGN.md §10).

    -1 -> SAN storage; >= NODE_OFFSET -> direct node id; else task id."""
    n_tasks = s.task_vm.shape[0]
    vm_host = _vm_host(c, meta, s)

    def node_of(task_idx):
        t = jnp.clip(task_idx, 0, n_tasks - 1)
        vm = jnp.maximum(s.task_vm[t], 0)
        node = jnp.where(task_idx < 0, c.storage_node, vm_host[vm])
        return jnp.where(task_idx >= NODE_OFFSET,
                         task_idx - NODE_OFFSET, node).astype(jnp.int32)
    return node_of(c.pkt_src_task), node_of(c.pkt_dst_task)


def _endpoint_cache(c: EngineConsts, meta, s: SimState):
    """Per-packet (src*n_nodes+dst) pair index, the ``RouteEnds`` its
    route is composed from, and reachability, derived purely from the
    current task placement.  Placement changes on only a
    handful of steps (admissions, failure re-placements), so ``_step``
    keeps this in the while-loop carry and refreshes it under a
    ``lax.cond`` instead of re-resolving every event (DESIGN.md §8).

    Packets whose endpoint task is currently UNPLACED get a garbage pair —
    harmless: with failures enabled ``_activate``'s ``_ep_placed`` check
    (which reads ``task_vm`` live) blocks them, and without failures every
    valid task of an admitted job is placed at admission."""
    src_node, dst_node = _pkt_endpoints(c, meta, s)
    pair = (src_node * meta.n_nodes + dst_node).astype(jnp.int32)
    # unreachable pairs (no candidate route, different nodes) never
    # activate -> the engine reports a stall instead of free transfer
    ends = route_ends(c, src_node, dst_node)
    reachable = (ends.n_cand > 0) | (src_node == dst_node)
    return {"pair": pair, "ends": ends, "reachable": reachable}


def _activate(c: EngineConsts, meta, pol, aux, cache, s: SimState):
    """Task activation then packet activation, both batched (DESIGN.md §8).

    The controller serializes packet arrivals — each SDN pick must see the
    channels admitted just before it — so activation scans a COMPACTED
    ready set (loop length = the live ready count, not the padded packet
    axis; index order preserved).  The legacy hash route needs no channel
    feedback: its picks are computed vectorially up front and the scan
    merely applies them while counting channels (a per-ready-packet
    update beats a packet-axis scatter on CPU for typical burst sizes).
    Steps where nothing becomes ready skip the routing work altogether
    (``lax.cond`` on the ready count).

    When the routing policy arrives host-static (``static_policy_value``,
    fleet cohorts — DESIGN.md §9) the dispatch specializes at trace time:
    legacy routing drops the scan entirely (no channel feedback, so one
    vectorized gather + scatter-add reproduces the sequential result
    bit-for-bit), and SDN routing precomputes the pop order with one sort
    so the scan body loses its per-iteration argmax + mask scatter.

    Returns ``(s, links, p_active, nc, link_bw)`` — the post-activation
    route-link tensor, active mask, per-link channel counts and effective
    link bandwidth are each computed ONCE here and threaded through rates
    and energy (the fused per-step network pass)."""
    # tasks: all inputs arrived
    t_ready = ((s.task_state == WAITING) & (s.task_got >= c.task_need)
               & (s.task_vm >= 0))
    task_state = jnp.where(t_ready, ACTIVE, s.task_state)
    task_start = jnp.where(t_ready, s.time, s.task_start)
    s = s._replace(task_state=task_state, task_start=task_start)

    # packets: job admitted & gate task done & endpoints routable (the
    # pair/reachability tensors come from the placement-change cache)
    gate = c.pkt_gate_task
    gate_ok = jnp.where(gate < 0, True,
                        s.task_state[jnp.maximum(gate, 0)] == DONE)
    admitted = s.job_admitted[jnp.maximum(c.pkt_job, 0)]
    p_ready = (s.pkt_state == WAITING) & admitted & gate_ok & c.pkt_valid
    pair_all, ends = cache["pair"], cache["ends"]
    p_ready = p_ready & cache["reachable"]
    if meta.has_failures:
        # a packet whose endpoint task was unplaced by a host failure must
        # wait for re-placement — its endpoints cannot resolve yet
        n_tasks = s.task_vm.shape[0]

        def _ep_placed(ref):
            is_task = (ref >= 0) & (ref < NODE_OFFSET)
            return jnp.where(is_task,
                             s.task_vm[jnp.clip(ref, 0, n_tasks - 1)] >= 0,
                             True)

        p_ready = (p_ready & _ep_placed(c.pkt_src_task)
                   & _ep_placed(c.pkt_dst_task))

    link_bw = _effective_link_bw(c, meta, s)

    def _apply_ready(s, cand, nc, links=None):
        # commit the activation: only ready packets change, so a step with
        # an empty ready set leaves (s, nc) bit-identical
        if links is None:
            with jax.named_scope("route_choice"):
                links = route_links(c, ends, cand)          # [P, H]
        if meta.has_failures:
            # a failure-reverted packet re-activates but keeps its FIRST
            # start: its measured duration includes the outage
            start_val = jnp.where(jnp.isnan(s.pkt_start), s.time,
                                  s.pkt_start)
        else:
            start_val = jnp.broadcast_to(s.time, s.pkt_start.shape)
        return s._replace(
            pkt_state=jnp.where(p_ready, ACTIVE, s.pkt_state),
            pkt_pair=jnp.where(p_ready, pair_all, s.pkt_pair),
            pkt_cand=jnp.where(p_ready, cand, s.pkt_cand),
            pkt_links=jnp.where(p_ready[:, None], links, s.pkt_links),
            pkt_start=jnp.where(p_ready, start_val, s.pkt_start)), nc

    routing_static = static_policy_value(pol["routing"])
    if routing_static is not None and routing_static != ROUTE_SDN:
        # static legacy: no channel feedback -> no scan.  Every ready
        # packet's hash pick and its route links are gathered at once and
        # the channel counts bumped by one order-independent integer
        # scatter-add — commutative, so bit-identical to the sequential
        # pop order the dynamic path preserves.
        with jax.named_scope("route_choice"):
            cand = legacy_route_choice(ends.n_cand, aux["pkt_hash"])
            links_all = route_links(c, ends, cand)  # [P, H]
        # channel bump over the ready set only — compacted pop-order scan
        # like the SDN branch minus the route choice (a whole-packet-axis
        # one-hot contraction moves ~100x more elements than the few ready
        # packets justify, and a packet-axis scatter serializes per row
        # under vmap).  The pop order is a cursor-chained masked min per
        # trip, NOT a precomputed sort: a packet-axis sort runs EVERY step
        # (most of which have an empty ready set) and was one of the
        # largest single per-step costs, while the per-trip min only runs
        # ``n_ready`` times.  Ascending index order is exactly what the
        # sort yielded — bit-identical.
        n_p = p_ready.shape[0]
        n_l = cache["nc"].shape[0]
        idx = jnp.arange(n_p, dtype=jnp.int32)
        n_ready = jnp.sum(p_ready.astype(jnp.int32))
        link_iota = jnp.arange(n_l, dtype=jnp.int32)
        links_safe = jnp.where(links_all >= 0, links_all, -1)

        def bump_one(k, carry):
            ch, cursor = carry
            i = jnp.min(jnp.where(p_ready & (idx > cursor), idx, n_p))
            links = links_safe[jnp.minimum(i, n_p - 1)]     # [H]
            ch = ch + jnp.sum((links[:, None] == link_iota[None, :])
                              .astype(jnp.int32), axis=0)
            return ch, i

        nc, _ = jax.lax.fori_loop(0, n_ready, bump_one,
                                  (cache["nc"], jnp.int32(-1)))
        s, nc = _apply_ready(s, cand, nc, links_all)
    elif routing_static == ROUTE_SDN:
        # static SDN: the controller feedback loop stays sequential, but
        # the scan body is restructured to be scatter-free — under vmap an
        # XLA/CPU scatter serializes one row per lane, so the two scatters
        # of the dynamic body dominate the whole step at fleet widths.
        # The pop order (ascending packet index — exactly what the
        # argmax-chain yields) comes from a cursor-chained masked min per
        # trip, NOT a precomputed packet-axis sort (which would run EVERY
        # step, ready set or not, and was one of the largest single
        # per-step costs); picks land in a POP-ORDER sequence at the
        # (unbatched) loop index — a dynamic_update_slice, not a scatter —
        # and are mapped back to the packet axis afterwards by a rank
        # gather; the channel bump is a dense one-hot compare-sum,
        # bit-identical to the scatter-add (integer adds of the same six
        # links).
        n_p = p_ready.shape[0]
        n_l = cache["nc"].shape[0]
        idx = jnp.arange(n_p, dtype=jnp.int32)
        rank = jnp.cumsum(p_ready.astype(jnp.int32)) - 1
        n_ready = jnp.sum(p_ready.astype(jnp.int32))
        link_iota = jnp.arange(n_l, dtype=jnp.int32)

        def act_sdn(k, carry):
            ch, cand_seq, cursor = carry
            i = jnp.min(jnp.where(p_ready & (idx > cursor), idx, n_p))
            with jax.named_scope("route_choice"):
                ends_i = ends.at(jnp.minimum(i, n_p - 1))
                routes_k = route_candidates(c, ends_i)
                cand = sdn_route_choice(routes_k, ends_i.n_cand, link_bw, ch)
                links = routes_k[cand]  # [H]
            bump = jnp.sum((links[:, None] == link_iota[None, :])
                           .astype(jnp.int32), axis=0)
            return ch + bump, \
                jax.lax.dynamic_update_index_in_dim(cand_seq, cand, k, 0), i

        nc, cand_seq, _ = jax.lax.fori_loop(
            0, n_ready, act_sdn,
            (cache["nc"], jnp.zeros(n_p, jnp.int32), jnp.int32(-1)))
        cand = cand_seq[jnp.maximum(rank, 0)]
        s, nc = _apply_ready(s, cand, nc)
    else:
        def activate_ready(args):
            s, nc = args
            # legacy flow = task-to-task connection (§4: "task-to-task
            # communication"); each flow picks its equal-hop route
            # independently at random and keeps it (§5.2).  No channel
            # feedback -> one shot (the flow hash is loop-invariant,
            # precomputed in ``aux``).
            with jax.named_scope("route_choice"):
                legacy_cand = legacy_route_choice(ends.n_cand,
                                                  aux["pkt_hash"])
            n_ready = jnp.sum(p_ready.astype(jnp.int32))
            is_sdn = pol["routing"] == ROUTE_SDN

            # one scan over the ready set only, in packet-index order (the
            # argmax-chain pops the first set bit each iteration — no sort,
            # no packet-axis scatter).  The carried ``nc`` doubles as the
            # controller's live view: each SDN pick sees the channels
            # admitted just before it, and the final value IS the
            # post-activation channel count (DESIGN.md §8).  SDN's global
            # view includes link liveness (link_bw has dead links at 0, so
            # their candidates lose the bottleneck argmax); the legacy
            # static hash is failure-blind and can re-pin the dead route.
            def act_one(_, carry):
                ch, cand_all, mask = carry
                i = jnp.argmax(mask).astype(jnp.int32)
                mask = mask.at[i].set(False)
                with jax.named_scope("route_choice"):
                    ends_i = ends.at(i)
                    routes_k = route_candidates(c, ends_i)
                    cand = jnp.where(
                        is_sdn,
                        sdn_route_choice(routes_k, ends_i.n_cand, link_bw, ch),
                        legacy_cand[i])
                    links = routes_k[cand]
                ch = ch.at[jnp.maximum(links, 0)].add(
                    (links >= 0).astype(jnp.int32))
                return ch, cand_all.at[i].set(cand), mask

            nc, cand, _ = jax.lax.fori_loop(0, n_ready, act_one,
                                            (nc, legacy_cand, p_ready))
            return _apply_ready(s, cand, nc)

        s, nc = jax.lax.cond(jnp.any(p_ready), activate_ready,
                             lambda args: args, (s, cache["nc"]))

    p_active = s.pkt_state == ACTIVE
    links = _route_links(s, p_active)
    return s, links, p_active, nc, link_bw


def _ctrl_request(c: EngineConsts, meta, pair, links, active_req,
                  pre_routed, t, tbl):
    """One flow's rule lookup + install request against the flow-table /
    controller carry (DESIGN.md §10).

    ``tbl`` = ``(ftab_pair, ftab_ready, ftab_stamp, ctrl_busy, ctrl_stamp,
    installs, evictions, reinstalls, queue_wait)``; returns
    ``(ready, tbl')`` where ``ready`` is the instant every rule on the
    route is usable.  ``active_req`` gates EVERY mutation (False = a pure
    lookup pass-through); ``pre_routed`` marks a flow that held a route
    before (its misses are churn: counted as reinstalls too).

    The route's switch hops are found from the link sources (routes are
    simple paths, so a route visits each switch at most once — the one-hot
    table writes below never collide).  Each miss takes one controller
    service slot FIFO behind ``ctrl_busy`` (``begin = max(t, busy)``,
    ``svc = misses / rate``) plus the flow-mod latency; cache hits are
    free but the flow still waits for any hit entry that is itself mid-
    install.  A missing rule lands in its switch's first empty slot, else
    the least-recently-stamped one (LRU); displacing a live entry counts
    an eviction.  With ``ctrl_slots == 0`` (no caching) every install is
    evicted immediately, so ``occupied == installs - evictions`` holds for
    every config (the conservation law, tests/test_fairshare.py).

    Controller failover (DESIGN.md §13): the primary is down on
    ``[ctrl_fail_t, ctrl_recover_t)``.  During the leader-election gap
    (the first ``ctrl_failover_delay`` seconds of the outage) install
    requests PARK — their service begin is pushed to the gap end and the
    parked seconds accumulate in the tbl's ``park`` slot; after the gap
    the backup serves with its own rate/latency until the primary
    recovers.  With ``ctrl_fail_t == inf`` every ``where`` below picks
    the primary branch, so pre-failover configs are numerically
    untouched."""
    (fpair, fready, fstamp, busy, stamp, installs, evicts, reinst,
     qwait, park) = tbl
    T = meta.ctrl_slots
    nodes = c.link_src[jnp.maximum(links, 0)]
    # switch node ids sit at [n_hosts, n_hosts + n_switches) — the PADDED
    # offsets in a packed sweep, same convention as the energy port count
    is_sw = ((links >= 0) & (nodes >= meta.n_hosts)
             & (nodes < meta.n_hosts + meta.n_switches))
    sw = jnp.where(is_sw, nodes - meta.n_hosts, 0)       # [H], clipped
    if T > 0:
        rows = fpair[sw]                                 # [H, T]
        hitmask = (rows == pair) & is_sw[:, None]
        hit = jnp.any(hitmask, axis=1)
        hit_ready = jnp.max(jnp.where(hitmask, fready[sw], -_INF))
    else:
        hit = jnp.zeros_like(is_sw)
        hit_ready = -_INF
    miss = is_sw & ~hit
    m = jnp.sum(miss.astype(jnp.int32))
    begin = jnp.maximum(t, busy)
    # failover: inside the primary outage the backup's rate/latency apply,
    # and requests landing in the leader-election gap park until it ends
    down = (t >= c.ctrl_fail_t) & (t < c.ctrl_recover_t)
    gap_end = jnp.minimum(c.ctrl_fail_t + c.ctrl_failover_delay,
                          c.ctrl_recover_t)
    rate = jnp.where(down, c.ctrl_backup_rate, c.ctrl_rate)
    lat = jnp.where(down, c.ctrl_backup_latency, c.ctrl_latency)
    begin2 = jnp.where(down, jnp.maximum(begin, gap_end), begin)
    svc = m.astype(jnp.float32) / rate                   # inf rate -> 0
    ready = jnp.maximum(jnp.maximum(
        jnp.where(m > 0, begin2 + svc + lat, -_INF),
        hit_ready), t)
    do_install = active_req & (m > 0)
    busy = jnp.where(do_install, begin2 + svc, busy)
    qwait = qwait + jnp.where(do_install, begin - t, 0.0)
    park = park + jnp.where(do_install, begin2 - begin, 0.0)
    installs = installs + jnp.where(active_req, m, 0)
    reinst = reinst + jnp.where(active_req & pre_routed, m, 0)
    if T > 0:
        sw_iota = jnp.arange(meta.n_switches, dtype=jnp.int32)
        new_stamp = stamp + 1
        # LRU victim per route hop: empty slots (key -1) win over any
        # stamp, then oldest stamp, ties to the lowest slot index
        key = jnp.where(rows < 0, -1, fstamp[sw])        # [H, T]
        slot = jnp.argmin(key, axis=1)                   # [H]
        displaced = jnp.take_along_axis(rows, slot[:, None],
                                        axis=1)[:, 0] >= 0
        evicts = evicts + jnp.where(
            do_install, jnp.sum((miss & displaced).astype(jnp.int32)), 0)
        # [H, SW, T] one-hot masks contracted over the route-hop axis —
        # NOT scatters (batched scatters serialize per lane, DESIGN.md §9)
        write_h = miss & do_install
        touch_h = hit & active_req
        sw_oh = (sw[:, None] == sw_iota[None, :]) & is_sw[:, None]
        slot_oh = slot[:, None] == jnp.arange(T, dtype=jnp.int32)[None, :]
        wmask = jnp.any(sw_oh[:, :, None]
                        & (write_h[:, None] & slot_oh)[:, None, :], axis=0)
        tmask = jnp.any(sw_oh[:, :, None]
                        & (hitmask & touch_h[:, None])[:, None, :], axis=0)
        fpair = jnp.where(wmask, pair, fpair)
        fready = jnp.where(wmask, ready, fready)
        fstamp = jnp.where(wmask | tmask, new_stamp, fstamp)
        stamp = jnp.where(active_req, new_stamp, stamp)
    else:
        # no caching: nothing is retained, so every install is counted
        # displaced immediately — the conservation law stays exact
        evicts = evicts + jnp.where(do_install, m, 0)
    return ready, (fpair, fready, fstamp, busy, stamp, installs, evicts,
                   reinst, qwait, park)


def _ctrl_tbl(s: SimState):
    return (s.ftab_pair, s.ftab_ready, s.ftab_stamp, s.ctrl_busy,
            s.ctrl_stamp, s.ctrl_installs, s.ctrl_evictions,
            s.ctrl_reinstalls, s.ctrl_queue_wait, s.ctrl_failover_park)


def _with_ctrl_tbl(s: SimState, tbl) -> SimState:
    (fpair, fready, fstamp, busy, stamp, installs, evicts, reinst,
     qwait, park) = tbl
    return s._replace(
        ftab_pair=fpair, ftab_ready=fready, ftab_stamp=fstamp,
        ctrl_busy=busy, ctrl_stamp=stamp, ctrl_installs=installs,
        ctrl_evictions=evicts, ctrl_reinstalls=reinst,
        ctrl_queue_wait=qwait, ctrl_failover_park=park)


def _activate_ctrl(c: EngineConsts, meta, pol, aux, cache, s: SimState):
    """Packet activation with the control plane in the loop (DESIGN.md
    §10) — replaces ``_activate``'s routing dispatch when
    ``meta.has_ctrl`` (``_activate`` itself is untouched: the off switch
    must trace the exact pre-control-plane program).

    One compacted pop-order scan (ascending packet index — the same order
    every plain path uses) over the union of the newly-ready set and the
    WAKE set: INSTALLING packets whose ``pkt_ready_t`` has arrived.  Per
    popped packet:

      * legacy routing bypasses the controller entirely — the static hash
        pick needs no flow-mod round trip — and activates immediately;
        that asymmetry is what lets legacy BEAT a slow controller
        (benchmarks/ctrl_sweep.py);
      * an SDN packet resolves its route (the stored candidate when the
        proactive pass pre-pinned one, else the live bottleneck pick) and
        requests its missing rules via ``_ctrl_request`` — unless the
        replica's ``ctrl_on`` is False (an identity-config lane in a mixed
        packed sweep bypasses the controller like legacy: zero counters).
        ``ready <= t`` (all rules cached and usable)
        activates in the SAME iteration, keeping the
        channel-bump order identical to the plain engine; otherwise the
        packet parks in INSTALLING with ``pkt_ready_t = ready`` joining
        the analytic dt min, and accrues ``pkt_install_wait``;
      * a woken packet activates unconditionally on its stored route: its
        rules WERE installed at request time, and later LRU churn only
        affects FUTURE flows — re-blocking a woken packet on a re-lookup
        could livelock two flows thrashing one slot.

    Only activating packets bump the channel counts (an INSTALLING packet
    holds no links), so the carried ``nc`` stays exact."""
    # tasks: identical to _activate
    t_ready = ((s.task_state == WAITING) & (s.task_got >= c.task_need)
               & (s.task_vm >= 0))
    s = s._replace(task_state=jnp.where(t_ready, ACTIVE, s.task_state),
                   task_start=jnp.where(t_ready, s.time, s.task_start))

    # ready set: same gates as _activate
    gate = c.pkt_gate_task
    gate_ok = jnp.where(gate < 0, True,
                        s.task_state[jnp.maximum(gate, 0)] == DONE)
    admitted = s.job_admitted[jnp.maximum(c.pkt_job, 0)]
    p_ready = (s.pkt_state == WAITING) & admitted & gate_ok & c.pkt_valid
    pair_all = cache["pair"]
    p_ready = p_ready & cache["reachable"]
    if meta.has_failures:
        n_tasks = s.task_vm.shape[0]

        def _ep_placed(ref):
            is_task = (ref >= 0) & (ref < NODE_OFFSET)
            return jnp.where(is_task,
                             s.task_vm[jnp.clip(ref, 0, n_tasks - 1)] >= 0,
                             True)

        p_ready = (p_ready & _ep_placed(c.pkt_src_task)
                   & _ep_placed(c.pkt_dst_task))
    p_wake = (s.pkt_state == INSTALLING) & (s.pkt_ready_t <= s.time)
    pop = p_ready | p_wake

    link_bw = _effective_link_bw(c, meta, s)
    n_p = pop.shape[0]
    n_l = cache["nc"].shape[0]
    idx = jnp.arange(n_p, dtype=jnp.int32)
    liota = jnp.arange(n_l, dtype=jnp.int32)
    n_pop = jnp.sum(pop.astype(jnp.int32))
    ends = cache["ends"]
    with jax.named_scope("route_choice"):
        legacy_cand = legacy_route_choice(ends.n_cand, aux["pkt_hash"])
    is_sdn = pol["routing"] == ROUTE_SDN
    t_now = s.time

    def pop_one(k, carry):
        (nc, pkt_state, pkt_pair, pkt_cand, pkt_links, pkt_start,
         pkt_ready_t, pkt_wait, tbl, cursor) = carry
        i = jnp.min(jnp.where(pop & (idx > cursor), idx, n_p))
        safe = jnp.minimum(i, n_p - 1)
        woken = p_wake[safe]
        # a pre-routed packet (woken, or pinned by the proactive pass)
        # keeps the route it holds
        pre_routed = pkt_cand[safe] >= 0
        pair = jnp.where(pre_routed, pkt_pair[safe], pair_all[safe])
        with jax.named_scope("route_choice"):
            ends_i = ends.at(safe)
            routes_k = route_candidates(c, ends_i)
            new_cand = jnp.where(
                is_sdn, sdn_route_choice(routes_k, ends_i.n_cand, link_bw, nc),
                legacy_cand[safe])
            cand = jnp.where(pre_routed, pkt_cand[safe], new_cand)
            links = jnp.where(pre_routed, pkt_links[safe],
                              routes_k[new_cand])        # [H]
        needs_ctrl = is_sdn & ~woken & c.ctrl_on
        ready, tbl = _ctrl_request(c, meta, pair, links, needs_ctrl,
                                   pre_routed & ~woken, t_now, tbl)
        act_now = woken | ~needs_ctrl | (ready <= t_now)
        oh = idx == i
        start_i = jnp.where(jnp.isnan(pkt_start[safe]), t_now,
                            pkt_start[safe])
        pkt_state = jnp.where(oh, jnp.where(act_now, ACTIVE, INSTALLING),
                              pkt_state)
        pkt_pair = jnp.where(oh, pair, pkt_pair)
        pkt_cand = jnp.where(oh, cand, pkt_cand)
        pkt_links = jnp.where(oh[:, None], links, pkt_links)
        pkt_start = jnp.where(oh, start_i, pkt_start)
        pkt_ready_t = jnp.where(oh, jnp.where(act_now, _INF, ready),
                                pkt_ready_t)
        pkt_wait = pkt_wait + jnp.where(
            oh & ~act_now, jnp.maximum(ready - t_now, 0.0), 0.0)
        bump = jnp.sum(((links[:, None] == liota[None, :])
                        & (links >= 0)[:, None]).astype(jnp.int32), axis=0)
        nc = nc + bump * act_now.astype(jnp.int32)
        return (nc, pkt_state, pkt_pair, pkt_cand, pkt_links, pkt_start,
                pkt_ready_t, pkt_wait, tbl, i)

    carry0 = (cache["nc"], s.pkt_state, s.pkt_pair, s.pkt_cand,
              s.pkt_links, s.pkt_start, s.pkt_ready_t, s.pkt_install_wait,
              _ctrl_tbl(s), jnp.int32(-1))
    (nc, pkt_state, pkt_pair, pkt_cand, pkt_links, pkt_start, pkt_ready_t,
     pkt_wait, tbl, _) = jax.lax.fori_loop(0, n_pop, pop_one, carry0)
    s = _with_ctrl_tbl(s._replace(
        pkt_state=pkt_state, pkt_pair=pkt_pair, pkt_cand=pkt_cand,
        pkt_links=pkt_links, pkt_start=pkt_start, pkt_ready_t=pkt_ready_t,
        pkt_install_wait=pkt_wait), tbl)

    p_active = s.pkt_state == ACTIVE
    links = _route_links(s, p_active)
    return s, links, p_active, nc, link_bw


def _preinstall(c: EngineConsts, meta, pol, aux, cache, s: SimState,
                admit_now) -> SimState:
    """Proactive flow-rule installation at job admission (DESIGN.md §10):
    scan the newly-admitted jobs' unrouted packets in index order, resolve
    each against the admission-time placement, install the missing rules
    (advancing the controller queue) and pin the route in
    ``pkt_pair``/``pkt_cand``.  The packets stay WAITING — their phase
    gates still apply — but by first use the rules are (usually) already
    cached, so the install latency overlaps compute instead of stalling
    the transfer; churn-evicted pins fall back to the reactive path and
    count as reinstalls.

    The route picks use a SCRATCH channel view (the live counts plus each
    earlier pin) so a job's flows spread over candidates the way the
    reactive controller would spread them — but pinned at admission time,
    blind to the traffic that develops later.  That lost adaptivity is
    proactive's intrinsic trade against reactive's install stall."""
    mask = (c.pkt_valid & admit_now[jnp.maximum(c.pkt_job, 0)]
            & (s.pkt_cand < 0) & cache["reachable"] & c.ctrl_on)
    pair_all = cache["pair"]
    link_bw = _effective_link_bw(c, meta, s)
    n_p = mask.shape[0]
    n_l = cache["nc"].shape[0]
    idx = jnp.arange(n_p, dtype=jnp.int32)
    liota = jnp.arange(n_l, dtype=jnp.int32)
    t_now = s.time

    def pre_one(k, carry):
        pkt_pair, pkt_cand, pkt_links, tbl, snc, cursor = carry
        i = jnp.min(jnp.where(mask & (idx > cursor), idx, n_p))
        safe = jnp.minimum(i, n_p - 1)
        pair = pair_all[safe]
        with jax.named_scope("route_choice"):
            ends_i = cache["ends"].at(safe)
            routes_k = route_candidates(c, ends_i)
            cand = sdn_route_choice(routes_k, ends_i.n_cand, link_bw, snc)
            links = routes_k[cand]
        _, tbl = _ctrl_request(c, meta, pair, links, jnp.asarray(True),
                               jnp.asarray(False), t_now, tbl)
        oh = idx == i
        pkt_pair = jnp.where(oh, pair, pkt_pair)
        pkt_cand = jnp.where(oh, cand, pkt_cand)
        pkt_links = jnp.where(oh[:, None], links, pkt_links)
        snc = snc + jnp.sum(((links[:, None] == liota[None, :])
                             & (links >= 0)[:, None]).astype(jnp.int32),
                            axis=0)
        return pkt_pair, pkt_cand, pkt_links, tbl, snc, i

    carry0 = (s.pkt_pair, s.pkt_cand, s.pkt_links, _ctrl_tbl(s),
              cache["nc"], jnp.int32(-1))
    pkt_pair, pkt_cand, pkt_links, tbl, _, _ = jax.lax.fori_loop(
        0, jnp.sum(mask.astype(jnp.int32)), pre_one, carry0)
    return _with_ctrl_tbl(
        s._replace(pkt_pair=pkt_pair, pkt_cand=pkt_cand,
                   pkt_links=pkt_links), tbl)


def _maybe_migrate(c: EngineConsts, meta, pol, s: SimState, cache):
    """Migrate-on-congestion dynamic placement (DESIGN.md §10, the S-CORE
    direction): at most one VM per step re-homes when its aggregate
    route-hop cost over active packets exceeds ``mig_threshold``.

    cost(v) = sum of current-route hop counts (``pair_hops``) over ACTIVE
    packets whose src or dst task runs on v.  The costliest eligible VM
    (over threshold, out of cooldown, global ``mig_limit`` not exhausted)
    moves to the live host minimizing the estimated cost — candidate-0
    hops of each of its packets' pairs with the VM's endpoint re-homed —
    requiring strict improvement over the same estimate at the current
    host.  The move is controller-mediated (one service slot), live: the
    VM's tasks keep their slot but execute nothing until ``vm_mig_until``
    (which joins the dt min), while every routed packet touching the VM
    reverts to WAITING through the PR-4 revert machinery (active ones
    release their channels) and re-routes against the new placement.

    Returns ``(s, cache, migrated)``; ``migrated`` forces the endpoint
    cache refresh in ``_step``."""
    mig_static = static_policy_value(pol["migration"])
    if mig_static is not None and mig_static != MIG_CONGESTION:
        return s, cache, jnp.asarray(False)
    n_vms = meta.n_vms
    n_t = s.task_vm.shape[0]
    n_p = s.pkt_state.shape[0]

    def attempt(args):
        s, nc0 = args
        t = s.time
        viota = jnp.arange(n_vms, dtype=jnp.int32)

        def ep_vm(ref):
            is_task = (ref >= 0) & (ref < NODE_OFFSET)
            vm = s.task_vm[jnp.clip(ref, 0, n_t - 1)]
            return jnp.where(is_task, vm, -1)            # [n_p]

        src_vm = ep_vm(c.pkt_src_task)
        dst_vm = ep_vm(c.pkt_dst_task)
        p_active = s.pkt_state == ACTIVE
        pair = jnp.maximum(s.pkt_pair, 0)
        cost_p = jnp.where(
            p_active, node_pair_hops(c, pair // meta.n_nodes,
                                     pair % meta.n_nodes), 0
        ).astype(jnp.float32)
        cost = (jnp.sum(jnp.where(src_vm[:, None] == viota[None, :],
                                  cost_p[:, None], 0.0), axis=0)
                + jnp.sum(jnp.where(dst_vm[:, None] == viota[None, :],
                                    cost_p[:, None], 0.0), axis=0))
        elig = ((viota < c.n_vms) & (cost > c.mig_threshold)
                & (t >= s.vm_mig_until + c.mig_cooldown)
                & (jnp.sum(s.vm_migrations) < c.mig_limit))
        any_elig = jnp.any(elig)
        v = jnp.argmax(jnp.where(elig, cost, -1.0)).astype(jnp.int32)

        # estimated cost of v's active flows per candidate home: move v's
        # endpoint to host h (hosts ARE nodes [0, n_hosts)), keep the
        # other end, read the candidate-0 hop count
        src_node, dst_node = _pkt_endpoints(c, meta, s)
        mine_s = p_active & (src_vm == v)
        mine_d = p_active & (dst_vm == v)
        mine = mine_s | mine_d
        n_h = c.host_fail_t.shape[0]
        hiota = jnp.arange(n_h, dtype=jnp.int32)
        new_src = jnp.where(mine_s[None, :], hiota[:, None],
                            src_node[None, :])
        new_dst = jnp.where(mine_d[None, :], hiota[:, None],
                            dst_node[None, :])
        last = meta.n_nodes - 1
        est = jnp.where(mine[None, :],
                        node_pair_hops(c, jnp.clip(new_src, 0, last),
                                       jnp.clip(new_dst, 0, last)), 0)
        est_cost = jnp.sum(est.astype(jnp.float32), axis=1)  # [n_h]
        host_live = hiota < c.n_hosts
        if meta.has_failures:
            host_live = host_live & ~s.host_dead
        cur_host = jnp.clip(s.vm_host[jnp.minimum(v, n_vms - 1)], 0,
                            n_h - 1)
        h_best = jnp.argmin(jnp.where(host_live, est_cost, _INF)
                            ).astype(jnp.int32)
        do = (any_elig & (est_cost[h_best] < est_cost[cur_host])
              & (h_best != cur_host))

        vm_oh = (viota == v) & do
        vm_host = jnp.where(vm_oh, h_best, s.vm_host)
        vm_mig_until = jnp.where(vm_oh, t + c.mig_cost, s.vm_mig_until)
        vm_migrations = s.vm_migrations + vm_oh.astype(jnp.int32)
        ctrl_busy = jnp.where(
            do, jnp.maximum(t, s.ctrl_busy) + 1.0 / c.ctrl_rate,
            s.ctrl_busy)

        # revert every routed packet touching v (active ones release their
        # channels via the compacted drop scan — PR-4 machinery)
        routed = (p_active | (s.pkt_state == INSTALLING)
                  | ((s.pkt_state == WAITING) & (s.pkt_cand >= 0)))
        hit_p = routed & ((src_vm == v) | (dst_vm == v)) & do
        hit_drop = hit_p & p_active
        links = _route_links(s, hit_drop)
        pidx = jnp.arange(n_p, dtype=jnp.int32)
        liota = jnp.arange(meta.n_links, dtype=jnp.int32)

        def drop_one(k, carry):
            nc, cursor = carry
            i = jnp.min(jnp.where(hit_drop & (pidx > cursor), pidx, n_p))
            links_k = links[jnp.minimum(i, n_p - 1)]
            nc = nc - jnp.sum((links_k[:, None] == liota[None, :])
                              .astype(jnp.int32), axis=0)
            return nc, i

        nc, _ = jax.lax.fori_loop(0, jnp.sum(hit_drop.astype(jnp.int32)),
                                  drop_one, (nc0, jnp.int32(-1)))
        s = s._replace(
            vm_host=vm_host, vm_mig_until=vm_mig_until,
            vm_migrations=vm_migrations, ctrl_busy=ctrl_busy,
            pkt_state=jnp.where(hit_p, WAITING, s.pkt_state),
            pkt_pair=jnp.where(hit_p, -1, s.pkt_pair),
            pkt_cand=jnp.where(hit_p, -1, s.pkt_cand),
            pkt_ready_t=jnp.where(hit_p, jnp.inf, s.pkt_ready_t),
            pkt_reroutes=s.pkt_reroutes + hit_p.astype(jnp.int32))
        return s, nc, do

    enabled = ((pol["migration"] == MIG_CONGESTION)
               & jnp.isfinite(c.mig_threshold))
    s, nc, migrated = jax.lax.cond(
        enabled, attempt, lambda args: (args[0], args[1],
                                        jnp.asarray(False)),
        (s, cache["nc"]))
    return s, {**cache, "nc": nc}, migrated


def _rates(c: EngineConsts, meta, pol, s: SimState, links, p_active,
           nc, link_bw):
    """Piecewise-constant packet/task rates from the fused network tensors
    (``links``/``p_active``/``nc``/``link_bw`` come straight from
    ``_activate`` — nothing here is recomputed, DESIGN.md §8).

    With clone slots provisioned (``meta.spec_slots > 0``, DESIGN.md §13)
    the speculative clones join the per-VM census — a clone steals fair
    share from its VM's resident tasks exactly like a real task — and the
    returned ``spec_rate`` carries their MIPS rates (``None`` when
    speculation is structurally off: the trace is unchanged)."""
    pkt_rate = fairshare.rates(pol["traffic"], links, p_active, link_bw,
                               meta.intra_bw, nc=nc)
    t_active = s.task_state == ACTIVE
    vm = jnp.maximum(s.task_vm, 0)
    # task-axis one-hot contraction, not a scatter (batched scatters
    # serialize per lane under vmap — DESIGN.md §9); int adds commute
    vm_iota = jnp.arange(c.vm_total_mips.shape[0], dtype=jnp.int32)
    n_on_vm = jnp.sum((vm[:, None] == vm_iota[None, :]) & t_active[:, None],
                      axis=0).astype(jnp.int32)
    s_active = None
    if meta.spec_slots > 0:
        s_active = s.spec_of >= 0
        svm = jnp.maximum(s.spec_vm, 0)
        n_on_vm = n_on_vm + jnp.sum(
            (svm[:, None] == vm_iota[None, :]) & s_active[:, None],
            axis=0).astype(jnp.int32)
    share = c.vm_total_mips[vm] / jnp.maximum(n_on_vm[vm], 1).astype(jnp.float32)
    task_rate = jnp.where(t_active, jnp.minimum(c.vm_core_mips[vm], share), 0.0)
    if meta.has_degradation:
        # gray windows throttle every task on the host (DESIGN.md §13);
        # scaling the final rate scales the per-core ceiling and the fair
        # share uniformly — the whole host is slow, not one VM
        hfac = _host_deg_factor(c, s)
        task_rate = task_rate * hfac[jnp.clip(
            _vm_host(c, meta, s)[vm], 0, c.host_slow_t.shape[0] - 1)]
    if meta.has_failures:
        # belt-and-braces: a task stranded on a dead host executes nothing
        # (can only happen when EVERY host was dead at placement time)
        task_rate = jnp.where(
            s.host_dead[jnp.clip(_vm_host(c, meta, s)[vm], 0,
                                 c.host_fail_t.shape[0] - 1)],
            0.0, task_rate)
    if meta.has_ctrl:
        # live migration (DESIGN.md §10): the VM keeps its tasks but
        # executes nothing until the re-homing completes; vm_mig_until is
        # a dt breakpoint, so the pause ends exactly on time
        task_rate = jnp.where(s.vm_mig_until[vm] > s.time, 0.0, task_rate)
    spec_rate = None
    if meta.spec_slots > 0:
        svm = jnp.maximum(s.spec_vm, 0)
        share_s = c.vm_total_mips[svm] / jnp.maximum(
            n_on_vm[svm], 1).astype(jnp.float32)
        spec_rate = jnp.where(
            s_active, jnp.minimum(c.vm_core_mips[svm], share_s), 0.0)
        host_of_clone = jnp.clip(_vm_host(c, meta, s)[svm], 0,
                                 c.host_fail_t.shape[0] - 1)
        if meta.has_degradation:
            spec_rate = spec_rate * _host_deg_factor(c, s)[host_of_clone]
        if meta.has_failures:
            spec_rate = jnp.where(s.host_dead[host_of_clone], 0.0,
                                  spec_rate)
        if meta.has_ctrl:
            spec_rate = jnp.where(s.vm_mig_until[svm] > s.time, 0.0,
                                  spec_rate)
    return pkt_rate, task_rate, t_active, spec_rate


def _speculate(c: EngineConsts, meta, pol, aux, s: SimState) -> SimState:
    """YARN speculative execution (DESIGN.md §13): in-loop straggler
    detection + clone launch into the statically pre-allocated per-job
    clone slots.  Only called when ``meta.spec_slots > 0``; the whole body
    is additionally gated on ``pol["speculation"] == SPEC_ON`` (trace-time
    skipped when the policy is statically off), so an off replica's state
    never moves.

    Two halves, both scatter-free one-hot contractions:

    * CLEANUP — a clone whose original left ACTIVE (finished first,
      failed-and-restarted, or reverted by an outage) or whose own host
      died is cancelled: its elapsed seconds land in ``spec_wasted`` and
      its VM container frees.  A task keeps ``task_cloned`` forever —
      one speculative attempt per task per run, like YARN's default.
    * LAUNCH — at most ONE clone per event step (the AM heartbeat batch):
      among ACTIVE tasks whose observed mean rate ``(mi - rem)/elapsed``
      is below HALF their job's live median rate (the per-job median is
      an O(n^2) pairwise rank count — no sort in the loop body,
      DESIGN.md §8), the slowest uncloned one with a free slot in its
      job's slot block gets a clone on the least-loaded live VM that
      avoids the original's host (so a gray host can't host both copies)
      and, when any exists, sits on a host OUTSIDE every current
      degradation window — a clone on a second browned-out host just
      doubles the waste.  The clone restarts from zero work —
      speculation races, it does not checkpoint."""
    spec_static = static_policy_value(pol["speculation"])
    if spec_static is not None and spec_static != SPEC_ON:
        return s
    S = s.spec_of.shape[0]
    n_t = s.task_rem.shape[0]
    n_j = s.job_admitted.shape[0]
    n_hosts_pad = c.host_fail_t.shape[0]
    t = s.time
    tiota = jnp.arange(n_t, dtype=jnp.int32)
    jiota = jnp.arange(n_j, dtype=jnp.int32)
    siota = jnp.arange(S, dtype=jnp.int32)
    vm_iota = jnp.arange(s.vm_load.shape[0], dtype=jnp.int32)
    slot_job = siota // meta.spec_slots
    vm_host = _vm_host(c, meta, s)

    def do_spec(s: SimState) -> SimState:
        # --- cleanup
        orig = jnp.maximum(s.spec_of, 0)
        live = s.spec_of >= 0
        gone = s.task_state[orig] != ACTIVE
        cancel = live & gone
        if meta.has_failures:
            clone_host = jnp.clip(vm_host[jnp.maximum(s.spec_vm, 0)], 0,
                                  n_hosts_pad - 1)
            cancel = cancel | (live & s.host_dead[clone_host])
        spec_wasted = s.spec_wasted + jnp.sum(
            jnp.where(cancel, t - s.spec_start, 0.0))
        vm_load = s.vm_load - jnp.sum(
            (jnp.maximum(s.spec_vm, 0)[:, None] == vm_iota[None, :])
            & cancel[:, None], axis=0).astype(jnp.int32)
        spec_of = jnp.where(cancel, -1, s.spec_of)

        # --- straggler detection (per-job live median of observed rates)
        elapsed = t - s.task_start
        el_ok = (s.task_state == ACTIVE) & c.task_valid & (elapsed > 1e-9)
        rate = jnp.where(el_ok, (c.task_mi - s.task_rem)
                         / jnp.maximum(elapsed, 1e-9), 0.0)
        job = jnp.maximum(c.task_job, 0)
        same = ((job[:, None] == job[None, :])
                & el_ok[:, None] & el_ok[None, :])
        lower = same & ((rate[None, :] < rate[:, None])
                        | ((rate[None, :] == rate[:, None])
                           & (tiota[None, :] < tiota[:, None])))
        n_peer = jnp.sum(same, axis=1)                 # includes self
        rank = jnp.sum(lower, axis=1)
        # exactly one median witness per job with >= 1 eligible task
        is_med = el_ok & (rank == n_peer // 2)
        med = jnp.sum(jnp.where(is_med[:, None]
                                & (job[:, None] == jiota[None, :]),
                                rate[:, None], 0.0), axis=0)  # [n_j]
        free = spec_of < 0
        job_free = jnp.sum((slot_job[:, None] == jiota[None, :])
                           & free[:, None], axis=0) > 0       # [n_j]
        straggler = (el_ok & ~s.task_cloned
                     & (2.0 * rate < med[job]) & job_free[job])

        # --- launch the slowest straggler (one per step)
        vm_live = jnp.arange(meta.n_vms) < c.n_vms
        if meta.has_failures:
            vm_live = vm_live & ~s.host_dead[
                jnp.clip(vm_host, 0, n_hosts_pad - 1)]
        launch = jnp.any(straggler) & jnp.any(vm_live)
        w = jnp.argmin(jnp.where(straggler, rate, _INF)).astype(jnp.int32)
        slot = jnp.min(jnp.where(free & (slot_job == job[w]), siota, S))
        slot = jnp.minimum(slot, S - 1)
        host_w = jnp.clip(vm_host[jnp.maximum(s.task_vm[w], 0)], 0,
                          n_hosts_pad - 1)
        off_host = vm_live & (jnp.clip(vm_host, 0, n_hosts_pad - 1)
                              != host_w)
        use = jnp.where(jnp.any(off_host), off_host, vm_live)
        if meta.has_degradation:
            undeg = use & (_host_deg_factor(c, s)[
                jnp.clip(vm_host, 0, n_hosts_pad - 1)] >= 1.0)
            use = jnp.where(jnp.any(undeg), undeg, use)
        pick = jnp.argmin(jnp.where(use, vm_load,
                                    jnp.iinfo(jnp.int32).max)
                          ).astype(jnp.int32)
        oh = (siota == slot) & launch
        return s._replace(
            spec_of=jnp.where(oh, w, spec_of),
            spec_vm=jnp.where(oh, pick, s.spec_vm),
            spec_rem=jnp.where(oh, c.task_mi[w], s.spec_rem),
            spec_start=jnp.where(oh, t, s.spec_start),
            task_cloned=s.task_cloned | ((tiota == w) & launch),
            vm_load=vm_load + ((vm_iota == pick) & launch
                               ).astype(jnp.int32),
            spec_launches=s.spec_launches + launch.astype(jnp.int32),
            spec_wasted=spec_wasted)

    return jax.lax.cond(pol["speculation"] == SPEC_ON, do_spec,
                        lambda s: s, s)


def _finished(c: EngineConsts, meta, s: SimState) -> jnp.ndarray:
    all_done = jnp.all(~c.job_valid | (s.job_out_done >= c.job_n_out))
    return all_done | s.stalled | (s.steps >= meta.max_steps)


def _make_aux(c: EngineConsts, pol) -> Dict[str, jnp.ndarray]:
    """Loop-invariant tensors hoisted out of the step body (DESIGN.md §8):
    the per-task placement hash and the per-packet legacy flow hash only
    depend on consts + the policy seed, so they are computed once before
    the while loop instead of every event."""
    n_t = c.task_job.shape[0]
    return {
        "task_hash": flow_hash_u32(jnp.arange(n_t, dtype=jnp.int32),
                                   c.task_job, pol["seed"]),
        "pkt_hash": flow_hash_u32(c.pkt_src_task + 1, c.pkt_dst_task + 1,
                                  pol["seed"]),
        # completion tolerances (also loop-invariant)
        "pkt_tol": c.pkt_bits * 1e-6 + 1.0,
        "task_tol": c.task_mi * 1e-6 + 1e-6,
    }


def _step(c: EngineConsts, meta, pol, aux, carry):
    """One event.  Every statement runs under one of six
    ``jax.named_scope`` phases, which XLA keeps in each op's ``op_name``,
    so a device trace splits the step's time by phase: ``admit_place``,
    ``activate``, ``chaos`` (failures, degradation, speculation),
    ``rates``, ``advance`` (dt-min, energy, clock) and ``complete``.

    ``aux["fail_fire"]``, where present, is the failure transitions'
    predicate decided outside a lane vmap (``make_fleet_chunk``)."""
    s, cache = carry
    if meta.has_failures:
        with jax.named_scope("chaos"):
            s, cache = _apply_failures(c, meta, pol, s, cache,
                                       aux.get("fail_fire"))
    with jax.named_scope("admit_place"):
        s, placed, admit_now = _admit_and_place(c, meta, pol, aux, s)
        if meta.has_ctrl:
            # migrate BEFORE the cache refresh so re-homed endpoints resolve
            # against the new placement this very step (DESIGN.md §10)
            s, cache, migrated = _maybe_migrate(c, meta, pol, s, cache)
            placed = placed | migrated
    with jax.named_scope("activate"):
        # placement changed -> the packet endpoint/pair cache is stale
        cache = jax.lax.cond(
            placed, lambda: {**cache, **_endpoint_cache(c, meta, s)},
            lambda: cache)
        # the fused network pass: route links, active mask, channel counts and
        # effective bandwidth come out of activation ONCE per step and feed
        # rates + energy below (DESIGN.md §8)
        if meta.has_ctrl:
            install_static = static_policy_value(pol["install_mode"])
            if install_static is None or install_static == INSTALL_PROACTIVE:
                s = jax.lax.cond(
                    (jnp.any(admit_now)
                     & (pol["install_mode"] == INSTALL_PROACTIVE)
                     & (pol["routing"] == ROUTE_SDN)),
                    lambda s: _preinstall(c, meta, pol, aux, cache, s,
                                          admit_now),
                    lambda s: s, s)
            s, links, p_active, nc, link_bw = _activate_ctrl(c, meta, pol, aux,
                                                             cache, s)
        else:
            s, links, p_active, nc, link_bw = _activate(c, meta, pol, aux,
                                                        cache, s)
    if meta.spec_slots > 0:
        with jax.named_scope("chaos"):
            # clone housekeeping + straggler launch happen AFTER activation
            # (so just-activated tasks are census-visible) and BEFORE rates
            # (so a launched clone shares its VM from this very interval)
            s = _speculate(c, meta, pol, aux, s)
    with jax.named_scope("rates"):
        pkt_rate, task_rate, t_active, spec_rate = _rates(c, meta, pol, s,
                                                          links, p_active,
                                                          nc, link_bw)

    with jax.named_scope("advance"):
        # earliest horizon (Eq. 4 generalized)
        dt_p = jnp.min(jnp.where(p_active & (pkt_rate > 0),
                                 s.pkt_rem / pkt_rate, _INF))
        dt_t = jnp.min(jnp.where(t_active & (task_rate > 0),
                                 s.task_rem / task_rate, _INF))
        future = (~s.job_admitted) & c.job_valid & (c.job_release > s.time)
        dt_r = jnp.min(jnp.where(future, c.job_release - s.time, _INF))
        dt = jnp.minimum(jnp.minimum(dt_p, dt_t), dt_r)
        if meta.has_failures:
            # fail/recover instants are rate breakpoints exactly like job
            # releases — they join the analytic min, no event heap needed
            # (DESIGN.md §7); ``fail_breaks`` is the four schedule tensors
            # pre-concatenated so this is ONE masked min (DESIGN.md §8)
            dt_f = jnp.min(jnp.where(c.fail_breaks > s.time,
                                     c.fail_breaks - s.time, _INF))
            dt = jnp.minimum(dt, dt_f)
        if meta.has_degradation:
            # gray-window edges are rate breakpoints exactly like outages
            # (DESIGN.md §13); ``deg_breaks`` pre-concatenates the four
            # schedule tensors so this is ONE masked min
            dt_d = jnp.min(jnp.where(c.deg_breaks > s.time,
                                     c.deg_breaks - s.time, _INF))
            dt = jnp.minimum(dt, dt_d)
        if meta.has_ctrl:
            # rule-install completions and migration resumes are rate
            # breakpoints exactly like failures (DESIGN.md §10): the analytic
            # min lands the clock exactly on each wake instant
            dt_c = jnp.min(jnp.where((s.pkt_state == INSTALLING)
                                     & (s.pkt_ready_t > s.time),
                                     s.pkt_ready_t - s.time, _INF))
            dt_m = jnp.min(jnp.where(s.vm_mig_until > s.time,
                                     s.vm_mig_until - s.time, _INF))
            dt = jnp.minimum(dt, jnp.minimum(dt_c, dt_m))
            # controller failover edges (primary down, election gap end,
            # primary back — DESIGN.md §13) are breakpoints too; all three
            # are inf when failover is unconfigured
            fo = jnp.stack([
                c.ctrl_fail_t,
                jnp.minimum(c.ctrl_fail_t + c.ctrl_failover_delay,
                            c.ctrl_recover_t),
                c.ctrl_recover_t])
            dt_fo = jnp.min(jnp.where(fo > s.time, fo - s.time, _INF))
            dt = jnp.minimum(dt, dt_fo)
        if meta.spec_slots > 0:
            # clone finishes join the min like task finishes
            dt_s = jnp.min(jnp.where((s.spec_of >= 0) & (spec_rate > 0),
                                     s.spec_rem / spec_rate, _INF))
            dt = jnp.minimum(dt, dt_s)
        stalled = jnp.isinf(dt)
        dt = jnp.where(stalled, 0.0, dt)

        # energy (power is constant over [t, t+dt))
        vm_safe = jnp.maximum(s.task_vm, 0)
        host_of_task = _vm_host(c, meta, s)[vm_safe]
        # MIPS-by-host via a compacted per-active-task accumulation, not a
        # task-axis scatter-add: the scatter runs EVERY step, and under a
        # vmapped cohort an XLA/CPU scatter serializes one row per lane
        # (DESIGN.md §9) — it alone cost the xl fleet ~10% batch efficiency.
        # Ascending task order is the scatter's own update order and the
        # skipped zero-adds are f32-exact (x + 0.0 == x away from -0.0/NaN,
        # and rate partial sums are finite and non-negative), so host_energy
        # stays bit-identical to the reference scatter.
        n_t_e = host_of_task.shape[0]
        hiota = jnp.arange(c.host_total_mips.shape[0], dtype=jnp.int32)
        order_e = jnp.sort(jnp.where(t_active,
                                     jnp.arange(n_t_e, dtype=jnp.int32), n_t_e))

        def mips_one(k, m):
            i = order_e[jnp.minimum(k, n_t_e - 1)]
            return m + jnp.where(hiota == host_of_task[i], task_rate[i], 0.0)

        mips_used = jax.lax.fori_loop(0, jnp.sum(t_active.astype(jnp.int32)),
                                      mips_one, jnp.zeros_like(c.host_total_mips))
        if meta.spec_slots > 0:
            # clones burn host cycles like real tasks; the slot axis is tiny
            # (n_jobs * spec_slots) so a dense one-hot contraction is cheaper
            # than extending the compacted loop
            clone_host = _vm_host(c, meta, s)[jnp.maximum(s.spec_vm, 0)]
            mips_used = mips_used + jnp.sum(
                jnp.where((s.spec_of >= 0)[:, None]
                          & (hiota[None, :] == clone_host[:, None]),
                          spec_rate[:, None], 0.0), axis=0)
        # utilization is relative to the CURRENT (possibly degraded) capacity:
        # a saturated gray host draws full power for less work (DESIGN.md §13);
        # _effective_host_mips is exactly host_total_mips when degradation is
        # off, keeping the off-switch trace unchanged
        util = jnp.clip(mips_used / jnp.maximum(_effective_host_mips(c, meta, s),
                                                1e-9), 0.0, 1.0)
        if meta.has_failures:
            util = jnp.where(s.host_dead, 0.0, util)  # dead hosts draw 0 W
        host_energy = s.host_energy + host_power(util, meta.energy) * dt
        host_busy = s.host_busy + jnp.where(util > 0, dt, 0.0)
        live_link = (nc > 0).astype(jnp.int32)
        if meta.has_failures:
            live_link = jnp.where(s.link_dead, 0, live_link)  # port is down
        # link-axis one-hot contraction, not two scatters (vmap serialization,
        # DESIGN.md §9); only the switch slice of the node axis is needed
        sw_iota = meta.n_hosts + jnp.arange(meta.n_switches, dtype=jnp.int32)
        sw_ports = jnp.sum(
            ((c.link_src[:, None] == sw_iota[None, :]).astype(jnp.int32)
             + (c.link_dst[:, None] == sw_iota[None, :]).astype(jnp.int32))
            * live_link[:, None], axis=0)
        switch_energy = s.switch_energy + switch_power(sw_ports, meta.energy) * dt

    with jax.named_scope("chaos"):
        if meta.has_failures:
            # per-job downtime: admitted, not done, and NOTHING of the job's
            # moves over [t, t+dt) — the failure-induced outage metric
            n_j = s.job_downtime.shape[0]
            prog_t = t_active & (task_rate > 0) & c.task_valid
            prog_p = p_active & (pkt_rate > 0) & c.pkt_valid
            # grouped ANY via one-hot masks, not two scatter-maxes (vmap
            # serialization, DESIGN.md §9); max over {0,1} == any
            jiota = jnp.arange(n_j, dtype=jnp.int32)
            job_prog = (
                jnp.any((jnp.maximum(c.task_job, 0)[:, None] == jiota[None, :])
                        & prog_t[:, None], axis=0)
                | jnp.any((jnp.maximum(c.pkt_job, 0)[:, None] == jiota[None, :])
                          & prog_p[:, None], axis=0)).astype(jnp.int32)
            job_live = (s.job_admitted & (s.job_out_done < c.job_n_out)
                        & c.job_valid)
            job_downtime = s.job_downtime + jnp.where(
                job_live & (job_prog == 0), dt, 0.0)
        else:
            job_downtime = s.job_downtime

        if meta.has_degradation:
            # wall-clock seconds with ANY live gray window open — the
            # degraded-exposure metric (same pass-through shape as
            # job_downtime: off-replicas in a packed sweep accumulate 0)
            any_deg = (jnp.any((c.host_slow_t <= s.time)
                               & (s.time < c.host_restore_t)
                               & (c.host_deg_factor != 1.0))
                       | jnp.any((c.link_slow_t <= s.time)
                                 & (s.time < c.link_restore_t)
                                 & (c.link_deg_factor != 1.0)))
            degraded_time = s.degraded_time + jnp.where(any_deg, dt, 0.0)
        else:
            degraded_time = s.degraded_time

    with jax.named_scope("advance"):
        time = s.time + dt
        pkt_rem = jnp.where(p_active, s.pkt_rem - pkt_rate * dt, s.pkt_rem)
        task_rem = jnp.where(t_active, s.task_rem - task_rate * dt, s.task_rem)
        steps = s.steps + 1

        if meta.has_ctrl:
            # count the primary→backup handover once, when the clock passes
            # ctrl_fail_t (a dt breakpoint, so the crossing is exact)
            crossed = (s.time <= c.ctrl_fail_t) & (time > c.ctrl_fail_t)
            ctrl_failovers = s.ctrl_failovers + crossed.astype(jnp.int32)
        else:
            ctrl_failovers = s.ctrl_failovers

    with jax.named_scope("complete"):
        p_done_now = p_active & (pkt_rem <= aux["pkt_tol"])
        t_done_now = t_active & (task_rem <= aux["task_tol"])

        pkt_state = jnp.where(p_done_now, DONE, s.pkt_state)
        pkt_finish = jnp.where(p_done_now, time, s.pkt_finish)
        task_state = jnp.where(t_done_now, DONE, s.task_state)
        task_finish = jnp.where(t_done_now, time, s.task_finish)

        # completions feed gates + release their channels.  Only a handful of
        # packets finish per event, so this is a compacted scan over the done
        # set — pop order is a cursor-chained masked min per trip (ascending
        # packet index, same order the old argmax-chain popped; a precomputed
        # packet-axis sort runs EVERY step, done set or not, and was one of
        # the largest single per-step costs) instead of three packet-axis
        # scatters (DESIGN.md §8).  The per-trip updates are one-hot
        # compare-sums, NOT scatters: under vmap an XLA/CPU scatter serializes
        # one row per lane, and at fleet widths the three scatters per trip
        # dominated the whole step.  All updates are commutative integer adds,
        # so the carried ``nc`` stays exact (mirroring activation's bumps) —
        # bit-identical.
        n_t_pad = s.task_got.shape[0]
        n_j_pad = s.job_out_done.shape[0]
        n_p_pad = p_done_now.shape[0]
        n_done = jnp.sum(p_done_now.astype(jnp.int32))
        idx_p = jnp.arange(n_p_pad, dtype=jnp.int32)
        liota = jnp.arange(nc.shape[0], dtype=jnp.int32)
        tiota = jnp.arange(n_t_pad, dtype=jnp.int32)
        jiota = jnp.arange(n_j_pad, dtype=jnp.int32)

        def complete_one(k, carry):
            nc_c, task_got, job_out_done, cursor = carry
            i = jnp.min(jnp.where(p_done_now & (idx_p > cursor), idx_p,
                                  n_p_pad))                 # k < n_done -> real
            safe = jnp.minimum(i, n_p_pad - 1)
            links_i = s.pkt_links[safe]
            nc_c = nc_c - jnp.sum((links_i[:, None] == liota[None, :])
                                  .astype(jnp.int32), axis=0)
            feeds_i = c.pkt_feeds_task[safe]
            task_got = task_got + (tiota == feeds_i).astype(jnp.int32)
            jtgt = jnp.where(feeds_i < 0, jnp.maximum(c.pkt_job[safe], 0), -1)
            job_out_done = job_out_done + (jiota == jtgt).astype(jnp.int32)
            return nc_c, task_got, job_out_done, i

        nc_next, task_got, job_out_done, _ = jax.lax.fori_loop(
            0, n_done, complete_one,
            (nc, s.task_got, s.job_out_done, jnp.int32(-1)))
        newly_job_done = (job_out_done >= c.job_n_out) & \
            (s.job_out_done < c.job_n_out) & c.job_valid
        job_done_t = jnp.where(newly_job_done, time, s.job_done_t)
        # task-axis one-hot contraction, not a scatter (same vmap reason);
        # integer adds commute -> bit-identical
        vm_iota = jnp.arange(s.vm_load.shape[0], dtype=jnp.int32)
        vm_load = s.vm_load - jnp.sum(
            (vm_safe[:, None] == vm_iota[None, :])
            & t_done_now[:, None], axis=0).astype(jnp.int32)

    with jax.named_scope("chaos"):
        spec_of, spec_rem = s.spec_of, s.spec_rem
        spec_wins, spec_wasted = s.spec_wins, s.spec_wasted
        if meta.spec_slots > 0:
            # clone completions: first finish WINS the race (DESIGN.md §13).
            # A tie on the same breakpoint goes to the original, so the
            # speculation axis can only ever help a task's finish time.
            s_orig = jnp.maximum(spec_of, 0)
            s_live = spec_of >= 0
            spec_rem = jnp.where(s_live, spec_rem - spec_rate * dt, spec_rem)
            clone_done = s_live & (spec_rem <= aux["task_tol"][s_orig])
            win = clone_done & ~t_done_now[s_orig]
            # task-axis effect of the wins (one-hot, not a scatter)
            win_t = jnp.sum((s_orig[:, None] == tiota[None, :])
                            & win[:, None], axis=0) > 0
            task_state = jnp.where(win_t, DONE, task_state)
            task_finish = jnp.where(win_t, time, task_finish)
            task_rem = jnp.where(win_t, 0.0, task_rem)
            # the losing copy frees its container: the overtaken ORIGINAL's VM
            # on a win, the clone's VM on every clone finish
            vm_load = vm_load - jnp.sum(
                (vm_safe[:, None] == vm_iota[None, :])
                & win_t[:, None], axis=0).astype(jnp.int32)
            vm_load = vm_load - jnp.sum(
                (jnp.maximum(s.spec_vm, 0)[:, None] == vm_iota[None, :])
                & clone_done[:, None], axis=0).astype(jnp.int32)
            # wasted seconds: the original's whole run on a win, the clone's
            # on a photo-finish loss (cancelled clones accrue in _speculate)
            waste = jnp.where(win, time - s.task_start[s_orig],
                              time - s.spec_start)
            spec_wasted = spec_wasted + jnp.sum(
                jnp.where(clone_done, waste, 0.0))
            spec_wins = spec_wins + jnp.sum(win.astype(jnp.int32))
            spec_of = jnp.where(clone_done, -1, spec_of)

    return s._replace(
        time=time, steps=steps, stalled=stalled,
        job_out_done=job_out_done, job_done_t=job_done_t,
        task_state=task_state, task_rem=task_rem, task_got=task_got,
        task_finish=task_finish,
        pkt_state=pkt_state, pkt_rem=pkt_rem, pkt_finish=pkt_finish,
        vm_load=vm_load, host_energy=host_energy, host_busy=host_busy,
        switch_energy=switch_energy, job_downtime=job_downtime,
        degraded_time=degraded_time, spec_of=spec_of, spec_rem=spec_rem,
        spec_wins=spec_wins, spec_wasted=spec_wasted,
        ctrl_failovers=ctrl_failovers), \
        {**cache, "nc": nc_next}


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def make_packed_simulator(meta):
    """Returns ``run(consts, policy_dict, s0=None) -> SimState`` with consts
    as an ARGUMENT, so a heterogeneous-scenario sweep can vmap over consts
    and policies together (see ``repro.scenarios.sweep``, DESIGN.md §5).

    ``meta`` is a ``SimMeta`` (a legacy meta dict is coerced): only static
    shapes + scalar params shared by every replica in the batch (padded
    maxima for a packed sweep).

    ``s0`` lets a caller pass the t=0 state in as a real argument —
    ``repro.api.runners`` builds it outside the jitted loop and DONATES its
    buffers, so XLA aliases them straight into the while-loop carry instead
    of materializing a second copy (DESIGN.md §8).  ``None`` derives it
    from consts, which is always equivalent.

    The finished flag rides in the loop carry: ``_finished`` is evaluated
    once per body on the advanced state instead of once in ``cond`` and
    again in ``body``, and the body is one ``lax.cond`` on the carried
    flag — a finished replica in a vmapped batch passes its state through
    (the batching rule turns the cond into the old per-leaf select), while
    an unbatched run skips even the selects.
    """
    meta = SimMeta.coerce(meta)

    def run(consts: EngineConsts, pol: Dict[str, jnp.ndarray],
            s0: SimState | None = None) -> SimState:
        if s0 is None:
            s0 = init_state_from_consts(consts, meta.n_switches,
                                        meta.ctrl_slots, meta.spec_slots)
        aux = _make_aux(consts, pol)
        # nothing is active at t=0, so the carried channel counts start 0
        cache0 = {**_endpoint_cache(consts, meta, s0),
                  "nc": jnp.zeros(meta.n_links, jnp.int32)}

        def cond(carry):
            _, _, done = carry
            return ~done

        def body(carry):
            s, cache, done = carry
            s, cache = jax.lax.cond(
                done, lambda sc: sc,
                lambda sc: _step(consts, meta, pol, aux, sc), (s, cache))
            return s, cache, _finished(consts, meta, s)

        s_final, _, _ = jax.lax.while_loop(
            cond, body, (s0, cache0, _finished(consts, meta, s0)))
        return s_final

    return run


def make_simulator(setup: SimSetup):
    """Returns a jit-able ``run(policy_dict) -> SimState`` closure."""
    consts, meta = make_consts(setup)
    run = make_packed_simulator(meta)
    return partial(run, consts)


# --- fleet chunk stepper (DESIGN.md §9) ------------------------------------


def tree_select(done, old, new):
    """Per-lane freeze: where ``done`` (a ``[W]`` bool), keep ``old``'s
    leaves, else take ``new``'s.  The fleet chunk applies it manually after
    an UNGUARDED vmapped step — a ``lax.cond`` on a batched done flag
    lowers to a select that still executes the step for every lane, and
    its both-branch machinery is ~40x slower than the step + select
    (DESIGN.md §9).  Running ``_step`` on a finished state is safe: its
    outputs are discarded here, and the compacted scans inside get zero
    trip counts."""
    def sel(a, b):
        d = done.reshape(done.shape + (1,) * (b.ndim - done.ndim))
        return jnp.where(d, a, b)
    return jax.tree_util.tree_map(sel, old, new)


def init_fleet_carry(consts: EngineConsts, meta, width: int):
    """The t=0 chunk carry for a ``width``-lane cohort sharing one consts:
    ``(SimState, step-cache, done)`` with every leaf gaining a leading lane
    axis.  Lanes start identical — policies differ, states don't."""
    meta = SimMeta.coerce(meta)
    s0 = init_state_from_consts(consts, meta.n_switches, meta.ctrl_slots,
                                meta.spec_slots)
    cache0 = {**_endpoint_cache(consts, meta, s0),
              "nc": jnp.zeros(meta.n_links, jnp.int32)}
    done0 = _finished(consts, meta, s0)
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (width,) + a.shape),
        (s0, cache0, done0))


def make_fleet_chunk(meta, static_pol=None, chunk_steps: int = 32,
                     consts_axes=None):
    """Build the fleet's K-step cohort stepper (DESIGN.md §9):
    ``chunk(consts, pol, carry) -> (carry, counts)`` advancing every live
    lane up to ``chunk_steps`` events, early-exiting when the whole cohort
    finishes.  ``counts`` is int32 ``[2]``: the chunk steps run, and how
    many of them took the failure transitions (always 0 without failures).

    ``consts_axes`` (default None: one consts shared by every lane) is a
    vmap in_axes pytree over ``EngineConsts`` — the streaming ring
    (DESIGN.md §11) maps the refillable job/task/packet leaves per lane
    (axis 0) while topology/cluster leaves stay shared (None), because
    lanes retire and reload ring slots at different times.

    ``carry`` is ``(SimState, cache, done)`` with a leading lane axis on
    every leaf (see ``init_fleet_carry``); ``pol`` holds the LANE-VARYING
    policy fields as ``[W]`` arrays, while ``static_pol`` carries the
    branch-selecting fields (routing / traffic / placement) as Python ints
    closed over at trace time — the cohort scheduler groups lanes so these
    are uniform, which is what lets ``_activate`` / ``_place_batch`` /
    ``fairshare.rates`` specialize their dispatch instead of executing
    both branches of a batched ``lax.cond`` (the batch wall).

    With failures the failure transitions' predicate is decided here,
    once per step for the cohort, outside the lane vmap, and passed into
    ``_step`` as ``aux["fail_fire"]``: "some live lane has a new death".
    Inside the vmap a per-lane predicate would turn the transitions'
    ``lax.cond`` into a select running them for every lane on every
    step; an unbatched one keeps it a cond.  A lane without a new death
    gets the identity from them, so results are unchanged.  Done lanes
    are masked out: a lane that finished exactly on a fail instant would
    otherwise report a "new" death on each of its frozen pseudo-steps,
    and its outputs are discarded.

    The caller jits (and on a multi-device mesh, shard_maps) the result;
    between chunk invocations the fleet scheduler retires finished lanes,
    compacts, and refills from its pending queue, so no lane runs more
    than ``chunk_steps - 1`` wasted events past its own finish."""
    meta = SimMeta.coerce(meta)
    static_pol = dict(static_pol or {})
    hoist = meta.has_failures

    def lane_step(consts, pol_lane, aux, sc):
        pol = {**pol_lane, **static_pol}
        s, cache = _step(consts, meta, pol, aux, sc)
        return s, cache, _finished(consts, meta, s)

    vrefresh = jax.vmap(_refresh_failures, in_axes=(consts_axes, 0))

    def chunk(consts, pol, carry):
        # loop-invariant per-lane tensors hoisted OUT of the while loop,
        # mirroring the serial runner (XLA does not reliably hoist them
        # out of a vmapped while body itself)
        if consts_axes is None:
            vaux = jax.vmap(
                lambda p: _make_aux(consts, {**p, **static_pol}))(pol)
        else:
            vaux = jax.vmap(
                lambda c_, p: _make_aux(c_, {**p, **static_pol}),
                in_axes=(consts_axes, 0))(consts, pol)
        aux_axes = {k: 0 for k in vaux}
        if hoist:
            aux_axes["fail_fire"] = None      # one flag for every lane
        vstep = jax.vmap(lane_step, in_axes=(consts_axes, 0, aux_axes, 0))

        def cond(c):
            i, (_s, _cache, done) = c[:2]
            return (i < chunk_steps) & ~jnp.all(done)

        def body(c):
            i, (s, cache, done) = c[:2]
            aux, fails = vaux, c[2:]
            if hoist:
                with jax.named_scope("chaos"):
                    _, new_h, new_l = vrefresh(consts, s)
                    fire = jnp.any((jnp.any(new_h, -1) | jnp.any(new_l, -1))
                                   & ~done)
                aux = {**vaux, "fail_fire": fire}
                fails = (fails[0] + fire.astype(jnp.int32),)
            s2, cache2, done2 = vstep(consts, pol, aux, (s, cache))
            # freeze the STATE of finished lanes (it is the result the
            # scheduler retires); the cache needs no select — it is never
            # read into results, a finished lane's pseudo-steps leave its
            # ready set empty, and a refill resets it from the t=0 carry.
            # The chunk loop is UNBATCHED (vmap is inside vstep), so this
            # cond really branches: with a well-bucketed cohort no lane is
            # done until the tail of the chunk and the whole-state select
            # (the widest memory traffic in the loop) is skipped.
            s = jax.lax.cond(jnp.any(done),
                             lambda: tree_select(done, s, s2),
                             lambda: s2)
            return (i + 1, (s, cache2, done | done2)) + fails

        init = (0, carry) + ((jnp.int32(0),) if hoist else ())
        i, carry, *fails = jax.lax.while_loop(cond, body, init)
        return carry, jnp.stack([i, fails[0] if hoist else jnp.int32(0)])

    return chunk


# --- deprecated shims ------------------------------------------------------
# The unified front door is ``repro.api`` (DESIGN.md §6): ``Experiment``
# dispatches single / policy-batch / packed-scenario execution through one
# compiled-runner cache, so repeated calls with an equal ``SimMeta`` reuse
# the traced program.  These wrappers keep the old spellings working and are
# proven bit-identical to the Experiment path by tests/test_api.py.


def simulate(setup: SimSetup, policy=None) -> SimState:
    """Deprecated shim: run one replica via the cached runner
    (policy: PolicyConfig, dict of scalars, or None for defaults).
    Prefer ``repro.api.Experiment(scenarios=setup, policies=policy).run()``.
    """
    from ..api import runners  # local import: api sits above core
    consts, meta = make_consts(setup)
    return runners.get_runner(meta, "single")(consts, as_policy_arrays(policy))


def simulate_batch(setup: SimSetup, pols: Dict[str, jnp.ndarray]) -> SimState:
    """Deprecated shim: vmap over a policy sweep — every dict value has a
    leading replica dim (missing registered fields broadcast their default).
    Prefer ``repro.api.Experiment``."""
    from ..api import runners
    consts, meta = make_consts(setup)
    pols = as_policy_arrays(pols)
    width = max((v.shape[0] for v in pols.values() if v.ndim), default=1)
    pols = {k: v if v.ndim else jnp.broadcast_to(v, (width,))
            for k, v in pols.items()}
    return runners.get_runner(meta, "policy_batch")(consts, pols)


def simulate_scenarios(consts: EngineConsts, meta,
                       pols: Dict[str, jnp.ndarray]) -> SimState:
    """Deprecated shim: ZIPPED batch over packed consts — every consts array
    and every policy value shares one leading replica dim R, and replica i
    runs consts[i] under pols[i].  Build consts with
    ``scenarios.sweep.pack_setups``; for the full scenario×policy cross
    product prefer ``repro.api.Experiment`` (or ``sweep_grid``), which nests
    the vmaps so consts broadcast over the policy axis."""
    from ..api import runners
    return runners.get_runner(SimMeta.coerce(meta), "zipped")(consts, pols)
