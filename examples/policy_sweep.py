"""Beyond-paper capability demo: a vmapped policy sweep — hundreds of
(routing x traffic x placement x job-selection x seed) scenarios as ONE
tensor program via ``repro.api.Experiment`` (DESIGN.md §6).  The Java
original runs one scenario per JVM invocation.

  PYTHONPATH=src python examples/policy_sweep.py --width 64
"""
import argparse
import itertools
import time

import jax
import numpy as np

from repro.api import Experiment, PolicyConfig
from repro.core import (JOBSEL_FCFS, JOBSEL_SJF, PLACE_LEAST_USED,
                        PLACE_RANDOM, ROUTE_LEGACY, ROUTE_SDN,
                        TRAFFIC_FAIRSHARE, TRAFFIC_WATERFILL, paper_setup)
from repro.util import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--width", type=int, default=32)
    args = ap.parse_args()

    setup = paper_setup(seed=0, split=2)
    combos = list(itertools.product(
        (ROUTE_SDN, ROUTE_LEGACY),
        (TRAFFIC_FAIRSHARE, TRAFFIC_WATERFILL),
        (PLACE_LEAST_USED, PLACE_RANDOM),
        (JOBSEL_FCFS, JOBSEL_SJF)))
    reps = max(1, args.width // len(combos))
    rows = [c + (s,) for s in range(reps) for c in combos][:args.width]
    pols = [PolicyConfig(routing=r, traffic=t, placement=p, job_selection=j,
                         job_concurrency=2, seed=s)
            for r, t, p, j, s in rows]
    exp = Experiment(scenarios=setup, policies=pols)

    t0 = time.time()
    res = exp.run()
    jax.block_until_ready(res.states.time)
    dt = time.time() - t0
    rep = res.job_report()
    en = res.energy_report()
    mean_ct = np.nanmean(rep["completion_measured"][0], axis=1)
    print(f"{len(pols)} simulations in {dt:.1f}s "
          f"({len(pols) / dt:.1f} sims/s, one tensor program)")
    names = {ROUTE_SDN: "sdn", ROUTE_LEGACY: "legacy"}
    tn = {TRAFFIC_FAIRSHARE: "eq3", TRAFFIC_WATERFILL: "waterfill"}
    pn = {PLACE_LEAST_USED: "least-used", PLACE_RANDOM: "random"}
    jn = {JOBSEL_FCFS: "fcfs", JOBSEL_SJF: "sjf"}
    print(f"{'routing':8} {'traffic':10} {'placement':11} {'jobsel':5} "
          f"{'mean-ct(s)':>10} {'energy(kWh)':>11}")
    best = np.argsort(mean_ct)
    for i in best[:8]:
        r = rows[i]
        print(f"{names[r[0]]:8} {tn[r[1]]:10} {pn[r[2]]:11} {jn[r[3]]:5} "
              f"{mean_ct[i]:10.1f} "
              f"{float(en['total_energy_j'][0, i]) / 3.6e6:11.2f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
