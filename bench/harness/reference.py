"""Plain reference of the simulated MapReduce-over-SDN semantics.

A straightforward event loop in NumPy, written from the model's rules and
independent of the code under test: it imports nothing of the program and
takes only the scenario as plain data (topology, cluster, job table,
outage windows).  From that it lowers the jobs into tasks and packets,
enumerates the equal-hop routes, and steps the simulation one event at a
time:

  outage transitions -> admission (FCFS) + least-used placement ->
  task activation -> packet activation in index order (legacy: flow hash
  over the equal-hop set; SDN: widest bottleneck given the channels
  admitted so far) -> Eq. 3 fair-share rates -> dt = earliest horizon ->
  energy += power * dt -> advance -> completions.

Arithmetic runs in float64.  ``quantize`` rounds every float the loop
stores (clock, remaining work, rates, energy) after each operation; with
``bfloat16_round`` it is the bfloat16 control that ``correct`` must reject.
"""
from __future__ import annotations

from collections import deque
from typing import Callable, Dict, List, Optional

import numpy as np

WAITING, ACTIVE, DONE = 0, 1, 2
ROUTE_LEGACY, ROUTE_SDN = 0, 1
_U32 = np.uint64(0xFFFFFFFF)


def bfloat16_round(x):
    """Round float64 values to the nearest bfloat16 (kept as float64)."""
    import ml_dtypes
    return np.asarray(x, np.float64).astype(ml_dtypes.bfloat16).astype(
        np.float64)


def _identity(x):
    return x


def flow_hash(a, b, seed) -> np.ndarray:
    """32-bit counter hash of (a, b, seed), top bit cleared."""
    def u32(v):
        return np.asarray(v, np.int64).astype(np.uint64) & _U32

    x = ((u32(a) * np.uint64(0x9E3779B1)) & _U32) \
        ^ ((u32(b) * np.uint64(0x85EBCA77)) & _U32) \
        ^ ((u32(seed) * np.uint64(0xC2B2AE3D)) & _U32)
    x = ((x ^ (x >> np.uint64(15))) * np.uint64(0x2C1B3C6D)) & _U32
    x = ((x ^ (x >> np.uint64(12))) * np.uint64(0x297A2D39)) & _U32
    x = x ^ (x >> np.uint64(15))
    return (x & np.uint64(0x7FFFFFFF)).astype(np.int64)


class Routes:
    """Equal-hop shortest routes per node pair, enumerated on demand.

    Candidates come in depth-first order, each node's out-links tried from
    the highest link index down, and at most ``k_max`` are kept."""

    def __init__(self, n_nodes: int, link_src, link_dst, k_max: int):
        self.n, self.k_max = n_nodes, k_max
        self.out: List[List] = [[] for _ in range(n_nodes)]
        for idx, (s, d) in enumerate(zip(link_src, link_dst)):
            self.out[int(s)].append((int(d), idx))
        self.dist = np.full((n_nodes, n_nodes), np.inf)
        for src in range(n_nodes):           # BFS from every node
            self.dist[src, src] = 0
            todo = deque([src])
            while todo:
                u = todo.popleft()
                for v, _ in self.out[u]:
                    if self.dist[src, v] == np.inf:
                        self.dist[src, v] = self.dist[src, u] + 1
                        todo.append(v)
        self._memo: Dict = {}

    def get(self, src: int, dst: int) -> List[List[int]]:
        key = (src, dst)
        if key not in self._memo:
            self._memo[key] = self._enumerate(src, dst)
        return self._memo[key]

    def _enumerate(self, src: int, dst: int) -> List[List[int]]:
        if src == dst or not np.isfinite(self.dist[src, dst]):
            return []
        found: List[List[int]] = []
        target = self.dist[src, dst]

        def walk(node, path):
            if len(found) >= self.k_max:
                return
            if node == dst:
                found.append(path)
                return
            for nxt, lidx in reversed(self.out[node]):
                if self.dist[src, node] + 1 + self.dist[nxt, dst] == target:
                    walk(nxt, path + [lidx])

        walk(src, [])
        return found


def lower_jobs(jobs, split: int):
    """Tasks and packets of a job table (paper Eqs. 1-2), in index order:
    per job its mappers then reducers; packets SAN->mapper, then every
    mapper->reducer pair, then reducer->SAN, each sent as ``split``
    packets.  Task index -1 is the SAN."""
    t_job, t_mi, t_need = [], [], []
    p_job, p_bits, p_gate, p_feeds, p_src, p_dst = [], [], [], [], [], []
    for j, job in enumerate(jobs):
        nm, nr = int(job["n_map"]), int(job["n_reduce"])
        base = len(t_job)
        maps = list(range(base, base + nm))
        reds = list(range(base + nm, base + nm + nr))
        for _ in maps:
            t_job.append(j), t_mi.append(job["map_mi"]), t_need.append(split)
        for _ in reds:
            t_job.append(j), t_mi.append(job["reduce_mi"])
            t_need.append(nm * split)

        def pkt(bits, gate, feeds, src, dst):
            p_job.append(j), p_bits.append(bits), p_gate.append(gate)
            p_feeds.append(feeds), p_src.append(src), p_dst.append(dst)

        for m in maps:
            for _ in range(split):
                pkt(job["input_gbits"] * 1e9 / (nm * split), -1, m, -1, m)
        for m in maps:
            for r in reds:
                for _ in range(split):
                    pkt(job["shuffle_gbits"] * 1e9 / (nm * nr * split),
                        m, r, m, r)
        for r in reds:
            for _ in range(split):
                pkt(job["output_gbits"] * 1e9 / (nr * split), r, -1, r, -1)
    i = lambda v: np.asarray(v, np.int64)             # noqa: E731
    f = lambda v: np.asarray(v, np.float64)           # noqa: E731
    return dict(t_job=i(t_job), t_mi=f(t_mi), t_need=i(t_need),
                p_job=i(p_job), p_bits=f(p_bits), p_gate=i(p_gate),
                p_feeds=i(p_feeds), p_src=i(p_src), p_dst=i(p_dst))


def simulate(sc: dict, routing: int, seed: int,
             quantize: Optional[Callable] = None,
             routes: Optional[Routes] = None) -> Dict[str, np.ndarray]:
    """Run one simulation of the plain scenario ``sc`` under ``routing``
    (0 legacy, 1 SDN) and hash ``seed``; returns the per-job done times,
    per-task and per-packet finish times, total energy (J), event steps
    and the stall flag."""
    q = quantize or _identity
    n_hosts, n_nodes = int(sc["n_hosts"]), int(sc["n_nodes"])
    l_src, l_dst = np.asarray(sc["link_src"]), np.asarray(sc["link_dst"])
    l_bw = q(np.asarray(sc["link_bw"], np.float64))
    n_links = l_bw.shape[0]
    vm_host = np.asarray(sc["vm_host"], np.int64)
    vm_total = q(np.asarray(sc["vm_total_mips"], np.float64))
    vm_core = q(np.asarray(sc["vm_core_mips"], np.float64))
    host_total = q(np.asarray(sc["host_total_mips"], np.float64))
    storage, intra_bw = int(sc["storage_node"]), q(float(sc["intra_bw"]))
    e = sc["energy"]
    jobs = sc["jobs"]
    lw = lower_jobs(jobs, int(sc["split"]))
    n_j, n_t, n_p = len(jobs), lw["t_job"].shape[0], lw["p_job"].shape[0]
    release = q(np.asarray([jb["submit_time"] for jb in jobs], np.float64))
    n_out = np.asarray([int(jb["n_reduce"]) * int(sc["split"])
                        for jb in jobs], np.int64)
    t_job, t_need = lw["t_job"], lw["t_need"]
    t_mi, p_bits = q(lw["t_mi"]), q(lw["p_bits"])
    p_job, p_gate, p_feeds = lw["p_job"], lw["p_gate"], lw["p_feeds"]
    p_src, p_dst = lw["p_src"], lw["p_dst"]
    p_tol = q(p_bits * 1e-6 + 1.0)
    t_tol = q(t_mi * 1e-6 + 1e-6)
    job_tasks = [np.flatnonzero(t_job == j) for j in range(n_j)]
    routes = routes or Routes(n_nodes, l_src, l_dst, int(sc["k_max"]))
    p_hash = flow_hash(p_src + 1, p_dst + 1, seed)

    fail = sc.get("failures")
    has_f = fail is not None
    if has_f:
        hf, hr = (np.asarray(fail[k], np.float64) for k in
                  ("host_fail_t", "host_recover_t"))
        lf, lr = (np.asarray(fail[k], np.float64) for k in
                  ("link_fail_t", "link_recover_t"))
        breaks = np.concatenate([hf, hr, lf, lr])
        n_events = int(np.isfinite(breaks).sum())
    # the model's step cap: the no-failure event bound, and with outages one
    # re-execution budget per instant, rounded up to a power of two
    max_steps = 4 * (n_p + n_t) + 4 * n_j + 64
    if has_f and np.isfinite(np.concatenate([hf, lf])).any():
        max_steps = max_steps * (1 + n_events) + 2 * n_events
        max_steps = 1 << (max_steps - 1).bit_length()

    t = 0.0
    admitted = np.zeros(n_j, bool)
    out_done = np.zeros(n_j, np.int64)
    done_t = np.full(n_j, np.nan)
    t_state = np.full(n_t, WAITING)
    t_rem = t_mi.copy()
    t_got = np.zeros(n_t, np.int64)
    t_vm = np.full(n_t, -1)
    t_finish = np.full(n_t, np.nan)
    p_state = np.full(n_p, WAITING)
    p_rem = p_bits.copy()
    p_start = np.full(n_p, np.nan)
    p_finish = np.full(n_p, np.nan)
    finite = routes.dist[np.isfinite(routes.dist)]
    p_links = np.full((n_p, max(1, int(finite.max()))), -1)
    vm_load = np.zeros(vm_host.shape[0], np.int64)
    host_energy = np.zeros(n_hosts)
    switch_energy = 0.0
    host_dead = np.zeros(n_hosts, bool)
    link_dead = np.zeros(n_links, bool)
    nc = np.zeros(n_links, np.int64)
    sw_lo, sw_hi = n_hosts, n_hosts + int(sc["n_switches"])
    steps, stalled = 0, False

    def node_of(task):
        return storage if task < 0 else int(vm_host[t_vm[task]])

    def place(tasks, vm_live):
        big = np.iinfo(np.int64).max
        for tk in tasks:
            pick = int(np.argmin(np.where(vm_live, vm_load, big)))
            vm_load[pick] += 1
            t_vm[tk] = pick

    def finished():
        return bool(np.all(out_done >= n_out)) or stalled \
            or steps >= max_steps

    while not finished():
        if has_f:                                    # outage transitions
            hd = (hf <= t) & (t < hr)
            ld = (lf <= t) & (t < lr)
            new_h, new_l = hd & ~host_dead, ld & ~link_dead
            host_dead, link_dead = hd, ld
            if new_h.any() or new_l.any():
                for i in np.flatnonzero(p_state == ACTIVE):
                    links = p_links[i][p_links[i] >= 0]
                    s_n, d_n = node_of(p_src[i]), node_of(p_dst[i])
                    ep = ((s_n < n_hosts and new_h[s_n])
                          or (d_n < n_hosts and new_h[d_n]))
                    if ep or new_l[links].any():
                        nc[links] -= 1
                        p_state[i] = WAITING
                        p_links[i] = -1
                        if ep:
                            p_rem[i] = p_bits[i]
                placed = t_vm >= 0
                hit = (placed & new_h[vm_host[np.maximum(t_vm, 0)]]
                       & ((t_state == ACTIVE) | (t_state == WAITING)))
                for tk in np.flatnonzero(hit):
                    vm_load[t_vm[tk]] -= 1
                    t_vm[tk] = -1
                    t_state[tk] = WAITING
                    t_rem[tk] = t_mi[tk]

        vm_live = np.ones(vm_host.shape[0], bool)  # admission + placement
        if has_f:
            vm_live &= ~host_dead[vm_host]
        released = ~admitted & (release <= t)
        running = int(np.sum(admitted & (out_done < n_out)))
        slots = max(int(sc["job_concurrency"]) - running, 0)
        if has_f and not vm_live.any():
            slots = 0
        cand = np.flatnonzero(released)
        for j in cand[np.argsort(release[cand], kind="stable")][:slots]:
            place(job_tasks[j], vm_live)
            admitted[j] = True
        if has_f and vm_live.any():
            orphan = ((t_vm < 0) & (t_state == WAITING)
                      & admitted[t_job])
            place(np.flatnonzero(orphan), vm_live)

        go = (t_state == WAITING) & (t_got >= t_need) & (t_vm >= 0)
        t_state[go] = ACTIVE                         # task activation

        gate_ok = np.where(p_gate < 0, True,
                           t_state[np.maximum(p_gate, 0)] == DONE)
        ready = (p_state == WAITING) & admitted[p_job] & gate_ok
        bw = np.where(link_dead, 0.0, l_bw)
        for i in np.flatnonzero(ready):              # packet activation
            if has_f and ((p_src[i] >= 0 and t_vm[p_src[i]] < 0)
                          or (p_dst[i] >= 0 and t_vm[p_dst[i]] < 0)):
                continue
            s_n, d_n = node_of(p_src[i]), node_of(p_dst[i])
            cands = routes.get(s_n, d_n)
            if not cands and s_n != d_n:
                continue                             # unreachable: waits
            if not cands:
                pick = []
            elif routing == ROUTE_SDN:
                bott = [min(q(bw[l] / (nc[l] + 1.0)) for l in c)
                        for c in cands]
                pick = cands[int(np.argmax(bott))]
            else:
                pick = cands[int(p_hash[i] % len(cands))]
            nc[pick] += 1
            p_links[i] = -1
            p_links[i, :len(pick)] = pick
            p_state[i] = ACTIVE
            if np.isnan(p_start[i]):
                p_start[i] = t

        act = np.flatnonzero(p_state == ACTIVE)     # rates (Eq. 3)
        share = q(bw / np.maximum(nc, 1))
        links = p_links[act]
        p_rate = np.where(links >= 0, share[np.maximum(links, 0)],
                          np.inf).min(axis=1)
        p_rate = np.where(np.isinf(p_rate), intra_bw, p_rate)
        t_act = t_state == ACTIVE
        vm = np.maximum(t_vm, 0)
        on_vm = np.bincount(vm[t_act], minlength=vm_host.shape[0])
        t_rate = np.minimum(vm_core[vm], q(vm_total[vm]
                                           / np.maximum(on_vm[vm], 1)))
        t_rate = np.where(t_act, t_rate, 0.0)
        if has_f:
            t_rate = np.where(host_dead[vm_host[vm]], 0.0, t_rate)

        horizons = [np.inf]                          # dt = earliest event
        m = p_rate > 0
        if m.any():
            horizons.append(np.min(q(p_rem[act][m] / p_rate[m])))
        m = t_act & (t_rate > 0)
        if m.any():
            horizons.append(np.min(q(t_rem[m] / t_rate[m])))
        fut = ~admitted & (release > t)
        if fut.any():
            horizons.append(np.min(q(release[fut] - t)))
        if has_f and (breaks > t).any():
            horizons.append(np.min(q(breaks[breaks > t] - t)))
        dt = min(horizons)
        stalled = bool(np.isinf(dt))
        dt = 0.0 if stalled else dt

        used = np.bincount(vm_host[vm[t_act]], weights=t_rate[t_act],
                           minlength=n_hosts)        # energy
        util = np.clip(q(used / np.maximum(host_total, 1e-9)), 0.0, 1.0)
        if has_f:
            util = np.where(host_dead, 0.0, util)
        power = np.where(util > 0, q(e["host_idle_w"] + q(
            util * (e["host_peak_w"] - e["host_idle_w"]))), 0.0)
        host_energy = q(host_energy + q(power * dt))
        live = (nc > 0) & ~link_dead
        ports = (np.bincount(l_src[live], minlength=n_nodes)
                 + np.bincount(l_dst[live], minlength=n_nodes))[sw_lo:sw_hi]
        sw_power = np.where(ports > 0, e["switch_static_w"]
                            + ports * e["switch_port_w"], 0.0)
        switch_energy = q(switch_energy + q(np.sum(sw_power) * dt))

        t = q(t + dt)                                # advance
        p_rem[act] = q(p_rem[act] - q(p_rate * dt))
        t_rem = np.where(t_act, q(t_rem - q(t_rate * dt)), t_rem)
        t_now = t_act & (t_rem <= t_tol)
        for i in act[p_rem[act] <= p_tol[act]]:      # completions
            p_state[i] = DONE
            p_finish[i] = t
            links = p_links[i][p_links[i] >= 0]
            nc[links] -= 1
            if p_feeds[i] >= 0:
                t_got[p_feeds[i]] += 1
            else:
                out_done[p_job[i]] += 1
        newly = (out_done >= n_out) & np.isnan(done_t)
        done_t[newly] = t
        for tk in np.flatnonzero(t_now):
            t_state[tk] = DONE
            t_finish[tk] = t
            vm_load[t_vm[tk]] -= 1
        steps += 1

    return {"job_done_t": done_t, "task_finish": t_finish,
            "pkt_finish": p_finish,
            "energy_j": float(np.sum(host_energy) + switch_energy),
            "steps": steps, "stalled": stalled}
