"""Configurations: the scenario a configuration file names, built through
the program's scenario registry, and the same scenario as plain data for
the reference (topology, cluster, job table, outage windows; nothing the
program derives from them, such as route tables or packed tensors)."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

CONFIG_DIR = Path(__file__).resolve().parents[1] / "configs"


def load_config(name: str) -> dict:
    with open(CONFIG_DIR / f"{name}.json") as f:
        cfg = json.load(f)
    if cfg["name"] != name:
        raise ValueError(f"{name}.json names itself {cfg['name']!r}")
    return cfg


def scenario(cfg: dict, workload_seed: int = 0):
    """The program's ``Scenario`` for this configuration."""
    from repro.scenarios import get_scenario
    return get_scenario(cfg["registry"], seed=int(workload_seed),
                        **cfg["scenario"])


def failure_injector(spec: dict):
    """The program's outage-trace injector for one failure-axis point."""
    from repro.scenarios.failures import failure_injector as inj
    return inj(host_rate=float(spec["host_rate"]), mttr=float(spec["mttr"]),
               horizon=float(spec["horizon"]), seed=int(spec["seed"]))


def plain(setup, cfg: dict, failures=None) -> dict:
    """The scenario of ``setup`` as plain data for ``reference.simulate``.
    ``failures`` is an outage schedule (or None)."""
    cl = setup.cluster
    topo = cl.topo
    e = cl.energy
    jobs = [dict(submit_time=float(j.submit_time), n_map=int(j.n_map),
                 n_reduce=int(j.n_reduce), map_mi=float(j.map_mi),
                 reduce_mi=float(j.reduce_mi),
                 input_gbits=float(j.input_gbits),
                 shuffle_gbits=float(j.shuffle_gbits),
                 output_gbits=float(j.output_gbits))
            for j in setup.jobs]
    sc = dict(
        n_hosts=int(topo.n_hosts), n_switches=int(topo.n_switches),
        n_nodes=int(topo.n_nodes),
        link_src=np.asarray(topo.link_src).tolist(),
        link_dst=np.asarray(topo.link_dst).tolist(),
        link_bw=np.asarray(topo.link_bw, np.float64).tolist(),
        vm_host=np.asarray(cl.vm_host).tolist(),
        vm_total_mips=np.asarray(cl.vm_total_mips, np.float64).tolist(),
        vm_core_mips=np.asarray(cl.vm_core_mips, np.float64).tolist(),
        host_total_mips=np.asarray(cl.host_total_mips, np.float64).tolist(),
        storage_node=int(cl.storage_node), intra_bw=float(cl.intra_bw),
        energy=dict(host_idle_w=float(e.host_idle_w),
                    host_peak_w=float(e.host_peak_w),
                    switch_static_w=float(e.switch_static_w),
                    switch_port_w=float(e.switch_port_w)),
        jobs=jobs, split=int(cfg["scenario"].get("split", 1)),
        k_max=int(cfg["scenario"]["k_max"]),
        job_concurrency=int(cfg["job_concurrency"]),
        failures=None)
    if failures is not None and failures.any_failures:
        sc["failures"] = {k: np.asarray(getattr(failures, k),
                                        np.float64).tolist()
                          for k in ("host_fail_t", "host_recover_t",
                                    "link_fail_t", "link_recover_t")}
    return sc
