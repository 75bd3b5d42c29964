"""SDN-vs-legacy under increasing failure rates (DESIGN.md §7).

The paper's §5 use-case (Figs. 11-13) compares SDN and legacy routing on a
healthy fabric; failure handling is where that comparison gets genuinely
interesting — the SDN controller's global view reroutes around a dead link
while the legacy static hash keeps forwarding into it.  This benchmark
runs the whole

    routing policy (sdn / legacy)  x  failure rate  x  trace seed

grid as ONE vmapped tensor program through ``repro.api.Experiment``: each
(rate, seed) cell is a deterministic ``FailureSchedule`` generated by
``scenarios.failures.failure_injector`` and becomes a scenario replica;
the two routing policies form the policy axis.  Reported per cell:
makespan, mean completion, re-executed tasks, rerouted packets and summed
job downtime.

  PYTHONPATH=src python benchmarks/failure_sweep.py
  PYTHONPATH=src python benchmarks/failure_sweep.py \
      --rates 0 2e-4 1e-3 --seeds 2 --json experiments/BENCH_failure_sweep.json
"""
import argparse
import re
import time

import jax
import numpy as np

try:
    from . import _cli            # python -m benchmarks.<name>
except ImportError:
    import _cli                   # python benchmarks/<name>.py

from repro.api import Experiment
from repro.core import (PolicyConfig, ROUTE_LEGACY, ROUTE_SDN, paper_jobs,
                        paper_setup)
from repro.scenarios.failures import failure_injector
from repro.util import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--rates", nargs="+", type=float,
                    default=[0.0, 1e-4, 3e-4, 1e-3],
                    help="host+link failure rates (per device per second)")
    ap.add_argument("--seeds", type=int, default=2,
                    help="failure-trace seeds per rate")
    ap.add_argument("--mttr", type=float, default=120.0,
                    help="mean seconds to repair")
    ap.add_argument("--horizon", type=float, default=5000.0,
                    help="failures beyond this instant are dropped")
    ap.add_argument("--n-each", type=int, default=1,
                    help="paper Table-3 job-mix multiplier")
    ap.add_argument("--concurrency", type=int, default=2)
    _cli.add_json_arg(ap)
    args = ap.parse_args(argv)

    t0 = time.time()
    setup = paper_setup(seed=0, jobs=paper_jobs(seed=0, n_each=args.n_each))
    injectors = [
        (f"r{rate:g}/s{seed}",
         failure_injector(host_rate=rate, link_rate=rate, mttr=args.mttr,
                          horizon=args.horizon, seed=seed))
        for rate in args.rates for seed in range(max(1, args.seeds))]
    exp = Experiment(
        scenarios=("paper-fabric", setup),
        policies=[("sdn", PolicyConfig(routing=ROUTE_SDN,
                                       job_concurrency=args.concurrency)),
                  ("legacy", PolicyConfig(routing=ROUTE_LEGACY,
                                          job_concurrency=args.concurrency))],
        failures=injectors,
    )
    jax.block_until_ready(exp.build()[0])   # consts on device, outside timers
    t_build = time.time() - t0

    t0 = time.time()
    res = exp.run()
    jax.block_until_ready(res.states.time)
    t_run = time.time() - t0

    n = len(res)
    print(f"{n} simulations ({res.n_scenarios} failure traces x "
          f"{res.n_policies} routing policies) in one vmapped grid: "
          f"setup {t_build:.1f}s, run {t_run:.1f}s")
    rows = res.rows()
    hdr = (f"{'trace':22} {'routing':8} {'makespan(s)':>11} "
           f"{'completion(s)':>13} {'reexec':>6} {'reroute':>7} "
           f"{'downtime(s)':>11}")
    print(hdr)
    print("-" * len(hdr))
    for row in rows:
        flag = "  STALLED" if row["stalled"] else ""
        print(f"{row['scenario']:22} {row['policy']:8} "
              f"{row['makespan_s']:11.1f} {row['mean_completion_s']:13.1f} "
              f"{row['task_reexecs']:6d} {row['pkt_reroutes']:7d} "
              f"{row['downtime_s']:11.1f}{flag}")

    # rate-aggregated SDN-vs-legacy deltas (the headline comparison);
    # the trace label embeds its rate (r<rate>/s<seed>), so aggregation
    # keys off the %g-rendered label — no row-ordering assumption, no
    # float round-trip
    by = {}
    for row in rows:
        m = re.search(r"/r([^/]+)/s\d+$", row["scenario"])
        key = m.group(1) if m else f"{args.rates[0]:g}"
        by.setdefault((key, row["policy"]), []).append(row)
    print("\nper-rate means (makespan s | downtime s):")
    for rate in args.rates:
        parts = []
        for pol in ("sdn", "legacy"):
            rs = by.get((f"{rate:g}", pol), [])
            mk = float(np.mean([r["makespan_s"] for r in rs])) if rs \
                else float("nan")
            dn = float(np.mean([r["downtime_s"] for r in rs])) if rs \
                else float("nan")
            parts.append(f"{pol} {mk:8.1f} | {dn:8.1f}")
        print(f"  rate={rate:g}: " + "   ".join(parts))

    if args.json:
        report = {
            "benchmark": "failure_sweep",
            "n_simulations": n,
            "rates": args.rates,
            "seeds": args.seeds,
            "mttr": args.mttr,
            "wall_s": {"setup": t_build, "run": t_run},
            "sims_per_s": n / t_run,
            "rows": rows,
        }
        _cli.write_report(report, args.json)


if __name__ == "__main__":
    enable_compile_cache()
    main()
