"""SDN-vs-legacy under a REAL control plane (DESIGN.md §10).

Every earlier benchmark gave SDN routing an instant-oracle controller:
flow rules appeared at activation time for free, so SDN could only win.
This benchmark prices the control plane — flow-rule install latency, a
rate-limited controller, LRU-bounded flow tables — and asks the question
the paper's §5 comparison cannot: *when does legacy routing beat SDN?*

The grid is

    routing (sdn / sdn-proactive / legacy)  x  install latency

run as ONE vmapped tensor program through ``repro.api.Experiment``'s
``ctrl=`` axis: each latency point becomes a scenario replica (the same
fabric, a different ``CtrlPlaneConfig``), the routing/install-mode
policies form the policy axis.  Legacy forwarding never touches the
controller, so its column is flat across latencies — the crossover row
where its makespan dips below reactive SDN's is the headline result.
Proactive install pre-pins routes at admission and overlaps the install
latency with job queueing, recovering most of the gap at the cost of
blind-to-traffic route choices and table churn (``rule_reinstalls``).

  PYTHONPATH=src python benchmarks/ctrl_sweep.py
  PYTHONPATH=src python benchmarks/ctrl_sweep.py \
      --latencies 0 0.01 0.05 0.2 --json experiments/BENCH_ctrl.json
"""
import argparse
import time

import jax

try:
    from . import _cli            # python -m benchmarks.<name>
except ImportError:
    import _cli                   # python benchmarks/<name>.py

from repro.api import Experiment
from repro.core import (CtrlPlaneConfig, INSTALL_PROACTIVE, PolicyConfig,
                        ROUTE_LEGACY, ROUTE_SDN)
from repro.util import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--latencies", nargs="+", type=float,
                    default=[0.005, 0.02, 0.05, 0.1],
                    help="per-rule install latencies (seconds)")
    ap.add_argument("--rate", type=float, default=500.0,
                    help="controller service rate (rules/second)")
    ap.add_argument("--slots", type=int, default=8,
                    help="flow-table slots per switch (LRU)")
    ap.add_argument("--scenario", default="paper-fabric",
                    help="registered scenario name to price the "
                    "controller on")
    ap.add_argument("--concurrency", type=int, default=2)
    _cli.add_json_arg(ap)
    args = ap.parse_args(argv)

    t0 = time.time()
    ctrl = [(f"lat{lat:g}",
             CtrlPlaneConfig(install_latency=lat, ctrl_rate=args.rate,
                             table_slots=args.slots))
            for lat in args.latencies]
    exp = Experiment(
        scenarios=args.scenario,
        policies=[
            ("sdn", PolicyConfig(routing=ROUTE_SDN,
                                 job_concurrency=args.concurrency)),
            ("sdn-pro", PolicyConfig(routing=ROUTE_SDN,
                                     install_mode=INSTALL_PROACTIVE,
                                     job_concurrency=args.concurrency)),
            ("legacy", PolicyConfig(routing=ROUTE_LEGACY,
                                    job_concurrency=args.concurrency)),
        ],
        ctrl=ctrl,
    )
    jax.block_until_ready(exp.build()[0])   # consts on device, off the clock
    t_build = time.time() - t0

    t0 = time.time()
    res = exp.run()
    jax.block_until_ready(res.states.time)
    t_run = time.time() - t0

    n = len(res)
    print(f"{n} simulations ({res.n_scenarios} ctrl configs x "
          f"{res.n_policies} policies) in one vmapped grid: "
          f"setup {t_build:.1f}s, run {t_run:.1f}s")
    rows = res.rows()
    hdr = (f"{'ctrl':24} {'policy':8} {'makespan(s)':>11} "
           f"{'instwait(s)':>11} {'installs':>8} {'evict':>6} "
           f"{'reinst':>6} {'qwait(s)':>9}")
    print(hdr)
    print("-" * len(hdr))
    for row in rows:
        flag = "  STALLED" if row["stalled"] else ""
        print(f"{row['scenario']:24} {row['policy']:8} "
              f"{row['makespan_s']:11.2f} {row['install_wait_s']:11.2f} "
              f"{row['rule_installs']:8d} {row['rule_evictions']:6d} "
              f"{row['rule_reinstalls']:6d} "
              f"{row['ctrl_queue_wait_s']:9.2f}{flag}")

    # the headline: latencies where the controller-free legacy path wins
    by = {}
    for row in rows:
        by.setdefault(row["scenario"], {})[row["policy"]] = row
    crossover = []
    for sname, cell in by.items():
        if {"sdn", "legacy"} <= cell.keys() \
                and cell["legacy"]["makespan_s"] < cell["sdn"]["makespan_s"]:
            crossover.append(sname)
    if crossover:
        print("\nlegacy beats reactive SDN at: " + ", ".join(crossover))
    else:
        print("\nno crossover in this latency range — SDN wins everywhere")

    if args.json:
        report = {
            "benchmark": "ctrl_sweep",
            "n_simulations": n,
            "scenario": args.scenario,
            "latencies": args.latencies,
            "ctrl_rate": args.rate,
            "table_slots": args.slots,
            "legacy_beats_sdn_at": crossover,
            "wall_s": {"setup": t_build, "run": t_run},
            "sims_per_s": n / t_run,
            "rows": rows,
        }
        _cli.write_report(report, args.json)


if __name__ == "__main__":
    enable_compile_cache()
    main()
