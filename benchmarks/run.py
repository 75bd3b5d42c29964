"""``python -m benchmarks.run`` — every paper table/figure + system benches.

One invocation regenerates every ``experiments/`` artifact: the paper
use-case figures, the system benches, and ALL the BENCH_*.json sweep
reports (scenario, failure, control-plane, fleet, engine profile,
streaming).  ``--full`` runs each sweep at its committed-baseline grid —
that is the pass that refreshes the perf-gate baselines
(``BENCH_engine.json`` / ``BENCH_fleet.json`` / ``BENCH_stream.json`` /
``BENCH_chaos.json``, whose CI gates re-run the same default grids); the default quick pass
uses the reduced CI grids and writes the gated benches to the
``*.ci.json`` artifact names, so a smoke run never clobbers a committed
baseline with a mismatched grid.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


# the suite timer is deliberate wall clock over whole child benchmarks
# (each syncs before its own timers); there is nothing here to block on
def main(argv=None):  # jaxcheck: disable=naked-timer
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="committed-baseline grids (refreshes BENCH_*.json "
                         "gate baselines); default is the quick CI pass")
    args = ap.parse_args(argv)
    quick = not args.full
    os.makedirs("experiments", exist_ok=True)
    results = {}
    t_all = time.time()

    from . import (advisor_validation, chaos_sweep, ctrl_sweep,
                   engine_profile, failure_sweep, fig11_13_usecase,
                   fleet_sweep, roofline_table, scenario_sweep,
                   sim_throughput, stream_sweep)

    def banner(step, title):
        print("=" * 72)
        print(f"[{step}/11] {title}")
        print("=" * 72)

    banner(1, "paper use-case (Figs. 11a/11b/12/13) — SDN vs legacy")
    results["fig11_13"] = fig11_13_usecase.main(quick=quick)
    json.dump(results["fig11_13"], open("experiments/fig11_13.json", "w"),
              indent=1)

    banner(2, "simulator throughput + vmapped policy sweeps")
    results["sim_throughput"] = sim_throughput.main(quick=quick)
    json.dump(results["sim_throughput"],
              open("experiments/sim_throughput.json", "w"), indent=1)

    banner(3, "collective-schedule advisor validation (DES vs analytic)")
    results["advisor"] = advisor_validation.main(quick=quick)
    json.dump(results["advisor"],
              open("experiments/advisor_validation.json", "w"), indent=1)

    banner(4, "roofline table (aggregated from dry-run artifacts)")
    results["roofline"] = roofline_table.main()

    # --- the post-seed sweep benches: quick = the CI bench-job grids,
    # --- full = the committed-baseline grids (each script's defaults)
    banner(5, "scenario sweep (topology x placement grid)")
    scenario_sweep.main(
        (["--scenarios", "paper-fabric", "leaf-spine"] if quick else [])
        + ["--json", "experiments/BENCH_scenario_sweep.json"])

    banner(6, "failure sweep (failure-rate x routing grid)")
    failure_sweep.main(
        (["--rates", "0", "3e-4", "--seeds", "1"] if quick else [])
        + ["--json", "experiments/BENCH_failure_sweep.json"])

    banner(7, "control-plane sweep (install-latency x routing grid)")
    ctrl_sweep.main(
        (["--latencies", "0.005", "0.05"] if quick else [])
        + ["--json", "experiments/BENCH_ctrl.json"])

    # the three GATED benches write the committed baseline path only on
    # --full (where the grid matches the CI gate); the quick pass writes
    # the .ci.json artifact names so a smoke run never clobbers a
    # baseline with a mismatched grid
    suffix = ".ci.json" if quick else ".json"

    banner(8, "fleet sweep (policy x failure-rate x seed cohorts)")
    fleet_sweep.main(
        (["--sims", "1000"] if quick else [])
        + ["--json", f"experiments/BENCH_fleet{suffix}"])

    banner(9, "engine step-kernel profile")
    engine_profile.main(
        (["--iters", "1"] if quick else ["--iters", "3"])
        + ["--json", f"experiments/BENCH_engine{suffix}"])

    banner(10, "streaming sweep (arrival rate x routing, slot ring)")
    stream_sweep.main(
        (["--horizon", "400"] if quick else [])
        + ["--json", f"experiments/BENCH_stream{suffix}"])

    banner(11, "chaos sweep (degradation severity x speculation grid)")
    chaos_sweep.main(
        (["--severities", "0.2", "0.4", "--seeds", "1"] if quick else [])
        + ["--json", f"experiments/BENCH_chaos{suffix}"])

    print("=" * 72)
    ok = results["fig11_13"]["qualitative_claim_reproduced"]
    print(f"benchmarks done in {time.time() - t_all:.0f}s; "
          f"paper qualitative claim reproduced: {ok}")
    return 0


if __name__ == "__main__":
    from repro.util import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
