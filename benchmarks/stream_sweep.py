"""Streaming sweep: sustained jobs/s and p99 sojourn across arrival rates
x routing, through the slot-recycling ring (DESIGN.md §11).

The finite-sweep benchmarks answer "how fast does a fixed job list
drain"; this one answers the steady-state question the streaming engine
exists for — what sustained load each routing policy holds and at what
tail latency — while also timing the ring itself (retire/refill + chunk
cadence) as wall-clock jobs/s.

The JSON report (``--json experiments/BENCH_stream.json``) is the
committed streaming perf trajectory; CI re-runs the same grid and fails
when aggregate wall-clock jobs/s regresses more than ``--max-regress``
(default 20%).

  PYTHONPATH=src python benchmarks/stream_sweep.py
  PYTHONPATH=src python benchmarks/stream_sweep.py \
      --json experiments/BENCH_stream.json
  PYTHONPATH=src python benchmarks/stream_sweep.py \
      --baseline experiments/BENCH_stream.json --max-regress 0.2
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
from repro.core import PolicyConfig, ROUTE_LEGACY, ROUTE_SDN
from repro.scenarios import get_scenario
from repro.scenarios.registry import stream_arrivals
from repro.util import enable_compile_cache

SCENARIO = "leaf-spine"
POLICIES = [
    ("sdn", PolicyConfig(routing=ROUTE_SDN, job_concurrency=4)),
    ("legacy", PolicyConfig(routing=ROUTE_LEGACY, job_concurrency=4)),
]


def run_rate(setup, rate: float, horizon: float, slots: int,
             chunk_steps: int) -> dict:
    """One open-arrival run at ``rate`` jobs/s; both routings ride as lanes
    of the same trace, so the comparison shares every arrival instant."""
    exp = Experiment(scenarios=(SCENARIO, setup), policies=POLICIES)
    arrivals = stream_arrivals(rate=rate, seed=0)
    t0 = time.perf_counter()
    res = exp.run_stream(arrivals, horizon, warmup=0.1 * horizon,
                         slots=slots, chunk_steps=chunk_steps)
    # sync before reading the clock so wall_jobs_per_s measures the
    # computation, not async dispatch (jaxcheck:naked-timer)
    jax.block_until_ready(res.jobs)
    wall = time.perf_counter() - t0
    jobs_total = sum(res.jobs[pi]["seq"].size for pi in range(res.n_policies))
    row = {
        "rate_jobs_s": rate,
        "trace_len": res.stats.trace_len,
        "refills": res.stats.refills,
        "chunks": res.stats.chunks,
        "wall_s": wall,
        "wall_jobs_per_s": jobs_total / wall,
        "policies": {},
    }
    for pi, pname in enumerate(res.policy_names):
        sm = res.summary(pi)
        row["policies"][pname] = {
            "throughput_jobs_s": sm["throughput_jobs_s"],
            "p50_sojourn_s": sm["p50_sojourn_s"],
            "p99_sojourn_s": sm["p99_sojourn_s"],
            "energy_j": sm["energy_j"],
            "slo": {k: v["attainment"] for k, v in sm["classes"].items()},
        }
    return row


def check_regression(report: dict, baseline_path: str,
                     max_regress: float) -> int:
    with open(baseline_path) as f:
        base = json.load(f)
    cur = report["aggregate_wall_jobs_per_s"]
    ref = base["aggregate_wall_jobs_per_s"]
    floor = ref * (1.0 - max_regress)
    status = "OK" if cur >= floor else "REGRESSED"
    print(f"stream gate: {cur:.1f} jobs/s vs baseline {ref:.1f} "
          f"(floor {floor:.1f}) {status}")
    if status != "OK":
        print(f"wall-clock jobs/s regression > {max_regress:.0%} "
              "(refresh the baseline in-PR if intentional)")
        return 1
    return 0


# the cold_s timer here deliberately measures wall clock INCLUDING
# compile and dispatch (run_rate syncs internally before returning)
def main(argv=None) -> int:  # jaxcheck: disable=naked-timer
    ap = argparse.ArgumentParser()
    ap.add_argument("--rates", nargs="+", type=float,
                    default=[0.05, 0.1, 0.2],
                    help="open arrival rates (jobs/s)")
    ap.add_argument("--horizon", type=float, default=1500.0,
                    help="arrival horizon (seconds of simulated time)")
    ap.add_argument("--slots", type=int, default=8,
                    help="ring capacity (jobs resident per lane)")
    ap.add_argument("--chunk-steps", type=int, default=128,
                    help="events per jitted chunk (K)")
    _cli.add_json_arg(ap)
    _cli.add_gate_args(ap, "BENCH_stream.json",
                       "allowed fractional wall-clock jobs/s drop")
    args = ap.parse_args(argv)

    setup = get_scenario(SCENARIO, n_jobs=2).build()
    # cold pass at the smallest rate compiles the chunk/refill/init
    # programs (one meta: the ring geometry is rate-independent)
    t0 = time.perf_counter()
    run_rate(setup, args.rates[0], min(args.horizon, 100.0), args.slots,
             args.chunk_steps)
    cold_s = time.perf_counter() - t0

    rows = []
    hdr = (f"{'rate':>6} {'jobs':>6} {'refills':>8} {'wall(s)':>8} "
           f"{'jobs/s(wall)':>13}  p99 sojourn (s) by policy")
    print(hdr)
    print("-" * len(hdr))
    for rate in args.rates:
        row = run_rate(setup, rate, args.horizon, args.slots,
                       args.chunk_steps)
        rows.append(row)
        p99s = "  ".join(
            f"{pn}={pv['p99_sojourn_s']:.1f}"
            for pn, pv in row["policies"].items())
        print(f"{rate:6.2f} {row['trace_len']:6d} {row['refills']:8d} "
              f"{row['wall_s']:8.2f} {row['wall_jobs_per_s']:13.1f}  {p99s}")

    wall = sum(r["wall_s"] for r in rows)
    jobs = sum(r["trace_len"] for r in rows) * len(POLICIES)
    report = {
        "benchmark": "stream_sweep",
        "backend": jax.default_backend(),
        "scenario": SCENARIO,
        "horizon_s": args.horizon,
        "slots": args.slots,
        "chunk_steps": args.chunk_steps,
        "cold_s": cold_s,
        "wall_s": wall,
        "aggregate_wall_jobs_per_s": jobs / wall,
        "rates": rows,
    }
    # sanity: the shared-trace design means both lanes retired every job
    for r in rows:
        for pv in r["policies"].values():
            assert np.isfinite(pv["p99_sojourn_s"])

    _cli.write_report(report, args.json)
    return _cli.gate(report, args, check_regression)


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
