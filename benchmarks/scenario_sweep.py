"""Scenario-diversity benchmark: topology x placement-policy grid as ONE
vmapped tensor program (paper contribution 6: "works for any topology").

Runs the paper's §5 fabric plus k-ary fat-tree, leaf-spine and
canonical-tree fabrics — each with its own workload shape — against
multiple placement policies through the unified ``repro.api.Experiment``
front door (DESIGN.md §6): padded to a common tensor shape and swept in a
single ``jit(vmap(...))`` call (DESIGN.md §5).

  PYTHONPATH=src python benchmarks/scenario_sweep.py
  PYTHONPATH=src python benchmarks/scenario_sweep.py \
      --scenarios paper-fabric fat-tree leaf-spine --seeds 2
  PYTHONPATH=src python benchmarks/scenario_sweep.py \
      --json experiments/BENCH_scenario_sweep.json
"""
import argparse
import time

import jax
import numpy as np

try:
    from . import _cli            # python -m benchmarks.<name>
except ImportError:
    import _cli                   # python benchmarks/<name>.py

from repro.api import Experiment
from repro.core import (PLACE_LEAST_USED, PLACE_RANDOM, PLACE_ROUND_ROBIN,
                        PolicyConfig)
from repro.scenarios import get_scenario, list_scenarios
from repro.util import enable_compile_cache

PLACEMENTS = (
    ("least-used", PLACE_LEAST_USED),
    ("random", PLACE_RANDOM),
    ("round-robin", PLACE_ROUND_ROBIN),
)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenarios", nargs="+",
                    default=["paper-fabric", "fat-tree", "leaf-spine",
                             "canonical-tree"],
                    help=f"registered scenarios ({', '.join(list_scenarios())})")
    ap.add_argument("--placements", type=int, default=2,
                    help="number of placement policies (1..3)")
    ap.add_argument("--seeds", type=int, default=1,
                    help="workload seeds per scenario")
    ap.add_argument("--concurrency", type=int, default=2)
    _cli.add_json_arg(ap, "write a machine-readable benchmark report "
                          "(wall times, steps/s, per-scenario rows)")
    args = ap.parse_args(argv)

    t0 = time.time()
    scens = [(f"{name}/s{seed}" if args.seeds > 1 else name,
              get_scenario(name, seed=seed).build())
             for name in args.scenarios for seed in range(args.seeds)]
    pols = [(pn, PolicyConfig(placement=pid, job_concurrency=args.concurrency))
            for pn, pid in PLACEMENTS[: max(1, args.placements)]]
    exp = Experiment(scenarios=scens, policies=pols)
    jax.block_until_ready(exp.build()[0])   # consts on device, outside timers
    t_build = time.time() - t0

    t0 = time.time()
    res = exp.run()
    jax.block_until_ready(res.states.time)
    t_first = time.time() - t0       # includes the one trace + compile

    t0 = time.time()
    res = exp.run()                  # cached runner: zero retraces
    jax.block_until_ready(res.states.time)
    t_run = time.time() - t0

    n = len(res)
    total_steps = int(np.asarray(res.states.steps).sum())
    print(f"{n} simulations ({res.n_scenarios} scenarios x "
          f"{res.n_policies} placements) in one vmapped batch: "
          f"setup {t_build:.1f}s, first run {t_first:.1f}s, "
          f"cached run {t_run:.1f}s ({n / t_run:.1f} sims/s, "
          f"{total_steps / t_run:.0f} steps/s)")
    print(f"padded shape: {res.meta.n_nodes} nodes, "
          f"{res.meta.n_links} links, {res.meta.n_vms} VMs")
    rows = res.rows()
    hdr = (f"{'scenario':24} {'placement':11} {'completion(s)':>13} "
           f"{'transmit(s)':>11} {'energy(kWh)':>11} {'makespan(s)':>11}")
    print(hdr)
    print("-" * len(hdr))
    for row in rows:
        flag = "  STALLED" if row["stalled"] else ""
        print(f"{row['scenario']:24} {row['policy']:11} "
              f"{row['mean_completion_s']:13.1f} "
              f"{row['mean_transmission_s']:11.1f} "
              f"{row['energy_kwh']:11.3f} {row['makespan_s']:11.1f}{flag}")

    if args.json:
        report = {
            "benchmark": "scenario_sweep",
            "n_simulations": n,
            "n_scenarios": res.n_scenarios,
            "n_policies": res.n_policies,
            "wall_s": {"setup": t_build, "first_run": t_first,
                       "cached_run": t_run},
            "sims_per_s": n / t_run,
            "total_steps": total_steps,
            "steps_per_s": total_steps / t_run,
            "padded_meta": {"n_nodes": res.meta.n_nodes,
                            "n_links": res.meta.n_links,
                            "n_vms": res.meta.n_vms,
                            "max_steps": res.meta.max_steps},
            "rows": rows,
        }
        _cli.write_report(report, args.json)


if __name__ == "__main__":
    enable_compile_cache()
    main()
