"""Routing x speculation under gray failure (DESIGN.md §13).

The failure sweep (DESIGN.md §7) killed devices outright; real clusters
mostly *limp* — thermally-throttled hosts, browned-out links, a primary
controller failing over to a slower backup.  This benchmark races the
full chaos stack:

    routing (sdn / legacy)  x  speculation (off / on)
        x  degradation severity  x  seed

on the ``leaf-spine-chaos`` scenario, as ONE vmapped tensor program:
each (severity, seed) pair becomes a scenario replica via the registered
factory's ``mean_factor`` / ``seed`` overrides (the same Clos, a
different seeded ``DegradationSchedule``), the routing/speculation
policies form the policy axis.  The headline is the speculation column:
YARN-style straggler cloning onto healthy VMs should cut the makespan on
every degraded replica, at a measured ``wasted_spec_work_s`` price.
``paper-fabric-chaos`` adds controller failover on top (--scenario).

  PYTHONPATH=src python benchmarks/chaos_sweep.py
  PYTHONPATH=src python benchmarks/chaos_sweep.py \
      --severities 0.2 0.5 --seeds 2 --json experiments/BENCH_chaos.json
"""
import argparse
import json
import sys
import time

import jax

try:
    from . import _cli            # python -m benchmarks.<name>
except ImportError:
    import _cli                   # python benchmarks/<name>.py

from repro.api import Experiment
from repro.core import (PolicyConfig, ROUTE_LEGACY, ROUTE_SDN, SPEC_OFF,
                        SPEC_ON)
from repro.scenarios import get_scenario
from repro.util import enable_compile_cache


def check_regression(report: dict, baseline_path: str,
                     max_regress: float) -> int:
    with open(baseline_path) as f:
        base = json.load(f)
    cur = report["sims_per_s"]
    ref = base["sims_per_s"]
    floor = ref * (1.0 - max_regress)
    status = "OK" if cur >= floor else "REGRESSED"
    print(f"chaos gate: {cur:.1f} sims/s vs baseline {ref:.1f} "
          f"(floor {floor:.1f}) {status}")
    if status != "OK":
        print(f"throughput regression > {max_regress:.0%} "
              "(refresh the baseline in-PR if intentional)")
        return 1
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--severities", nargs="+", type=float,
                    default=[0.2, 0.4, 0.6],
                    help="mean in-window rate multipliers (lower = worse "
                    "gray failure)")
    ap.add_argument("--seeds", type=int, default=3,
                    help="degradation-trace seeds per severity")
    ap.add_argument("--scenario", default="leaf-spine-chaos",
                    help="registered chaos scenario factory "
                    "(leaf-spine-chaos / paper-fabric-chaos)")
    ap.add_argument("--spec-slots", type=int, default=2,
                    help="clone slots per job")
    ap.add_argument("--concurrency", type=int, default=2)
    _cli.add_json_arg(ap)
    _cli.add_gate_args(ap, "BENCH_chaos.json",
                       "allowed fractional sims/s drop")
    args = ap.parse_args(argv)

    t0 = time.time()
    scens = [(f"sev{sev:g}-s{seed}",
              get_scenario(args.scenario, mean_factor=sev, seed=seed,
                           spec_slots=args.spec_slots).build())
             for sev in args.severities for seed in range(args.seeds)]
    exp = Experiment(
        scenarios=scens,
        policies=[
            ("sdn", PolicyConfig(routing=ROUTE_SDN, speculation=SPEC_OFF,
                                 job_concurrency=args.concurrency)),
            ("sdn-spec", PolicyConfig(routing=ROUTE_SDN,
                                      speculation=SPEC_ON,
                                      job_concurrency=args.concurrency)),
            ("legacy", PolicyConfig(routing=ROUTE_LEGACY,
                                    speculation=SPEC_OFF,
                                    job_concurrency=args.concurrency)),
            ("legacy-spec", PolicyConfig(routing=ROUTE_LEGACY,
                                         speculation=SPEC_ON,
                                         job_concurrency=args.concurrency)),
        ],
    )
    jax.block_until_ready(exp.build()[0])   # consts on device, off the clock
    t_build = time.time() - t0

    t0 = time.time()
    res = exp.run()
    jax.block_until_ready(res.states.time)
    t_run = time.time() - t0

    n = len(res)
    print(f"{n} simulations ({res.n_scenarios} chaos traces x "
          f"{res.n_policies} policies) in one vmapped grid: "
          f"setup {t_build:.1f}s, run {t_run:.1f}s")
    rows = res.rows()
    hdr = (f"{'trace':14} {'policy':12} {'makespan(s)':>11} "
           f"{'degr(s)':>8} {'clones':>6} {'wins':>5} {'waste(s)':>9} "
           f"{'fo':>3} {'park(s)':>8}")
    print(hdr)
    print("-" * len(hdr))
    for row in rows:
        flag = "  STALLED" if row["stalled"] else ""
        print(f"{row['scenario']:14} {row['policy']:12} "
              f"{row['makespan_s']:11.2f} {row['degraded_time_s']:8.1f} "
              f"{row['spec_launches']:6d} {row['spec_wins']:5d} "
              f"{row['wasted_spec_work_s']:9.2f} {row['failover_count']:3d} "
              f"{row['failover_park_s']:8.2f}{flag}")

    # the headline: traces where cloning stragglers cuts the makespan
    by = {}
    for row in rows:
        by.setdefault(row["scenario"], {})[row["policy"]] = row
    spec_wins, deltas = [], []
    for sname, cell in by.items():
        if {"sdn", "sdn-spec"} <= cell.keys():
            d = cell["sdn"]["makespan_s"] - cell["sdn-spec"]["makespan_s"]
            deltas.append(d / max(cell["sdn"]["makespan_s"], 1e-9))
            if d > 1e-3:
                spec_wins.append(sname)
    mean_gain = sum(deltas) / len(deltas) if deltas else 0.0
    print(f"\nspeculation cuts the SDN makespan on {len(spec_wins)}/"
          f"{len(by)} traces (mean gain {mean_gain:.1%})")

    report = {
        "benchmark": "chaos_sweep",
        "n_simulations": n,
        "scenario": args.scenario,
        "severities": args.severities,
        "seeds": args.seeds,
        "spec_slots": args.spec_slots,
        "speculation_wins_at": spec_wins,
        "mean_speculation_gain": mean_gain,
        "wall_s": {"setup": t_build, "run": t_run},
        "sims_per_s": n / t_run,
        "rows": rows,
    }
    _cli.write_report(report, args.json)
    return _cli.gate(report, args, check_regression)


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
