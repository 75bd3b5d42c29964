"""Fleet sweep: a 10,000-simulation policy × failure-rate × seed grid as
ONE program (DESIGN.md §9).

This is the headline the fleet execution layer exists for — the paper's
"one experiment run answers a whole design question" pitch at a scale the
serial runner cannot touch: routing {legacy, sdn} × placement {least-used,
round-robin} × host-failure rate {0, 2, 5, 10 %/s·host} × hundreds of
seeds, drained in a single ``Experiment.run_fleet`` invocation through
chunked early-exit cohorts (sharded over every visible device).  Results
are bit-identical to the serial runner — proven by tests/test_fleet.py on
the same machinery, not re-proven here (a serial 10k-sim run is exactly
the wall this engine cracks).

The JSON report (``--json experiments/BENCH_fleet.json``) is the committed
fleet perf trajectory; CI re-runs a reduced grid and fails when aggregate
sims/s regresses more than ``--max-regress`` (default 20%).

  PYTHONPATH=src python benchmarks/fleet_sweep.py
  PYTHONPATH=src python benchmarks/fleet_sweep.py \
      --json experiments/BENCH_fleet.json
  PYTHONPATH=src python benchmarks/fleet_sweep.py --sims 1000 \
      --baseline experiments/BENCH_fleet.json --max-regress 0.2
"""
import argparse
import json
import sys
import time

import jax
import numpy as np

try:
    from . import _cli            # python -m benchmarks.<name>
except ImportError:
    import _cli                   # python benchmarks/<name>.py

from repro.api import Experiment
from repro.scenarios.failures import failure_injector
from repro.util import enable_compile_cache

SCENARIO = "paper-fabric"
ROUTINGS = (("legacy", 0), ("sdn", 1))
PLACEMENTS = (("least-used", 0), ("round-robin", 1))
FAIL_RATES = (0.0, 0.02, 0.05, 0.10)


def build_grid(n_sims: int) -> Experiment:
    """policy × failure-rate × seed grid with ~n_sims cells (rounded down
    to a whole number of seeds per policy point)."""
    points = len(ROUTINGS) * len(PLACEMENTS) * len(FAIL_RATES)
    n_seeds = max(1, n_sims // points)
    pols = [(f"{rn}/{pn}/s{s}", dict(routing=r, placement=p, seed=s))
            for rn, r in ROUTINGS for pn, p in PLACEMENTS
            for s in range(n_seeds)]
    fails = [(f"host{int(rate * 100)}pct",
              failure_injector(host_rate=rate, mttr=20.0, horizon=500.0))
             for rate in FAIL_RATES]
    return Experiment(scenarios=SCENARIO, policies=pols, failures=fails)


def summarize(res) -> dict:
    """Per-(failure-rate, routing) means — the design-question readout."""
    rep = res.job_table() if hasattr(res, "job_table") else None
    del rep  # results surface varies; completion means below suffice
    comp = {}
    done_t = np.asarray(res.states.job_done_t)          # [S, P, n_jobs]
    valid = np.asarray(res.consts.job_valid)            # [S, n_jobs]
    for si, sname in enumerate(res.scenario_names):
        for rn, _ in ROUTINGS:
            sel = [pi for pi, pn in enumerate(res.policy_names)
                   if pn.startswith(rn + "/")]
            v = done_t[si][sel][:, valid[si]]
            comp[f"{sname}/{rn}"] = {
                "mean_job_done_t": float(np.nanmean(
                    np.where(np.isfinite(v), v, np.nan))),
                "finished_frac": float(np.isfinite(v).mean()),
            }
    return comp


def check_regression(report: dict, baseline_path: str,
                     max_regress: float) -> int:
    with open(baseline_path) as f:
        base = json.load(f)
    cur, ref = report["aggregate_sims_per_s"], base["aggregate_sims_per_s"]
    floor = ref * (1.0 - max_regress)
    status = "OK" if cur >= floor else "REGRESSED"
    print(f"fleet gate: {cur:.0f} sims/s vs baseline {ref:.0f} "
          f"(floor {floor:.0f}) {status}")
    if status != "OK":
        print(f"aggregate sims/s regression > {max_regress:.0%} "
              "(refresh the baseline in-PR if intentional)")
        return 1
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sims", type=int, default=10_000,
                    help="grid size (policy x failure-rate x seed cells)")
    ap.add_argument("--width", type=int, default=128,
                    help="fleet cohort width")
    ap.add_argument("--chunk-steps", type=int, default=64,
                    help="events per jitted chunk (K)")
    _cli.add_json_arg(ap)
    _cli.add_gate_args(ap, "BENCH_fleet.json",
                       "allowed fractional aggregate sims/s drop")
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    exp = build_grid(args.sims)
    n = len(exp.scenarios) * len(exp.policies)
    build_s = time.perf_counter() - t0
    print(f"grid: {len(exp.scenarios)} failure rates x "
          f"{len(exp.policies)} policies = {n} sims "
          f"(built in {build_s:.2f}s)")

    # cold run: compiles every cohort program and calibrates the step
    # predictor; the timed run below is the steady-state fleet number
    t0 = time.perf_counter()
    res, stats = exp.run_fleet(width=args.width,
                               chunk_steps=args.chunk_steps,
                               return_stats=True)
    jax.block_until_ready(res.states)
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    res, stats = exp.run_fleet(width=args.width,
                               chunk_steps=args.chunk_steps,
                               return_stats=True)
    # sync before reading the clock so the number is the computation,
    # not jax's async dispatch (jaxcheck:naked-timer)
    jax.block_until_ready(res.states)
    wall_s = time.perf_counter() - t0
    agg = n / wall_s

    print(f"cold (compile+calibrate): {cold_s:.1f}s; "
          f"timed: {n} sims in {wall_s:.1f}s = {agg:.0f} sims/s")
    print(f"cohorts={stats.cohorts} chunks={stats.chunks} "
          f"refills={stats.refills} width={stats.width} "
          f"devices={stats.devices}")

    report = {
        "benchmark": "fleet_sweep",
        "backend": jax.default_backend(),
        "scenario": SCENARIO,
        "sims": n,
        "width": args.width,
        "chunk_steps": args.chunk_steps,
        "devices": stats.devices,
        "cohorts": stats.cohorts,
        "chunks": stats.chunks,
        "refills": stats.refills,
        "build_s": build_s,
        "cold_s": cold_s,
        "wall_s": wall_s,
        "aggregate_sims_per_s": agg,
        "summary": summarize(res),
    }

    _cli.write_report(report, args.json)
    return _cli.gate(report, args, check_regression)


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
