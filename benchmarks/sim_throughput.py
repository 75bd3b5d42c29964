"""Simulator scaling benchmark (beyond paper): events/sec and the vmapped
policy-sweep capability the Java original lacks (one scenario per JVM run
vs thousands of replicas per tensor program here).

Runs through the unified ``repro.api`` front door (DESIGN.md §6): the
compiled-runner cache makes the compile-once / run-many split explicit.
"""
from __future__ import annotations

import json
import time
from typing import Dict

import jax

from repro.api import Experiment, PolicyConfig, runners
from repro.core import ROUTE_LEGACY, ROUTE_SDN, paper_setup
from repro.core.engine import make_consts
from repro.core.policies import as_policy_arrays
from repro.util import enable_compile_cache


def single_run_events_per_sec(setup) -> Dict[str, float]:
    consts, meta = make_consts(setup)
    run = runners.get_runner(meta, "single")
    pol = as_policy_arrays(PolicyConfig())
    jax.block_until_ready(consts)   # device transfer outside the timers
    t0 = time.perf_counter()
    s = run(consts, pol)
    jax.block_until_ready(s.time)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    n = 5
    for _ in range(n):
        s = run(consts, pol)
        jax.block_until_ready(s.time)
    dt = (time.perf_counter() - t0) / n
    return {"events": int(s.steps), "run_s": dt,
            "events_per_s": float(s.steps) / dt, "compile_s": compile_s}


def sweep_scaling(setup, widths=(1, 8, 32)) -> Dict[str, Dict]:
    out = {}
    for w in widths:
        pols = [PolicyConfig(routing=ROUTE_SDN if i % 2 == 0 else ROUTE_LEGACY,
                             job_concurrency=2, seed=i) for i in range(w)]
        exp = Experiment(scenarios=setup, policies=pols)
        jax.block_until_ready(exp.build()[0])
        t0 = time.perf_counter()
        res = exp.run()
        jax.block_until_ready(res.states.time)
        compile_and_run = time.perf_counter() - t0
        t0 = time.perf_counter()
        res = exp.run()
        jax.block_until_ready(res.states.time)
        run_s = time.perf_counter() - t0
        out[str(w)] = {"replicas": w, "run_s": run_s,
                       "replicas_per_s": w / run_s,
                       "first_call_s": compile_and_run}
    return out


def main(quick: bool = False) -> Dict:
    setup = paper_setup(seed=0, split=2)
    single = single_run_events_per_sec(setup)
    sweep = sweep_scaling(setup, widths=(1, 8) if quick else (1, 8, 32))
    base = sweep["1"]["run_s"]
    print(f"sim_throughput: {single['events_per_s']:.0f} events/s "
          f"({single['events']} events in {single['run_s'] * 1e3:.0f} ms)")
    for w, r in sweep.items():
        speedup = (base * int(w)) / r["run_s"]
        print(f"  vmap x{w:>3}: {r['run_s'] * 1e3:8.0f} ms "
              f"({speedup:4.1f}x vs sequential singles)")
    print(f"  engine traces this process: {runners.trace_count()} "
          f"(cached runners: {runners.cache_size()})")
    return {"single": single, "sweep": sweep,
            "engine_traces": runners.trace_count()}


if __name__ == "__main__":
    enable_compile_cache()
    json.dump(main(), open("experiments/sim_throughput.json", "w"), indent=1)
