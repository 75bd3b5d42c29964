"""Drive one rehearsal run of a cell with a fault planted in the program
under test, and print whether the run judged itself correct:

    python bench/tests/faults.py <fault> <cell>

Faults:
  frozen_step   the engine step leaves the state as it was (only its step
                counter moves)
  half_batch    the second half of a batch is left out: its lanes take the
                first half's results (policy batch) or policies (fleet)
  altered       a done time is altered where the step produces it
"""
import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]


def plant(fault: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.api import fleet, runners
    from repro.core import engine

    if fault == "frozen_step":
        def step(c, meta, pol, aux, carry):
            s, cache = carry
            return s._replace(steps=s.steps + 1), cache
        engine._step = step
    elif fault == "altered":
        real = engine._step

        def step(c, meta, pol, aux, carry):
            s, cache = real(c, meta, pol, aux, carry)
            return s._replace(job_done_t=s.job_done_t * 1.001), cache
        engine._step = step
    elif fault == "half_batch":
        real_runner = runners.get_runner

        def get_runner(meta, kind):
            fn = real_runner(meta, kind)
            if kind != "policy_batch":
                return fn

            def call(consts, pols):
                out = fn(consts, pols)
                return jax.tree_util.tree_map(
                    lambda a: jnp.concatenate(
                        [a[: (a.shape[0] + 1) // 2]] * 2)[: a.shape[0]], out)
            return call
        runners.get_runner = get_runner
        real_lanes = fleet._lane_policies

        def lanes(pol_np, sched):
            out = real_lanes(pol_np, sched)
            half = (sched.width + 1) // 2
            return {k: np.concatenate([v[:half]] * 2)[: v.shape[0]]
                    for k, v in out.items()}
        fleet._lane_policies = lanes
    else:
        raise SystemExit(f"unknown fault {fault!r}")
    runners.cache_clear()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("fault")
    ap.add_argument("cell")
    a = ap.parse_args()
    from harness import core
    plant(a.fault)
    args = argparse.Namespace(workload=a.cell, seed=987654321987,
                              seconds=1.0, trace=0, rehearse=True)
    out = core.run_cell(args, time.perf_counter())
    print(json.dumps({"correct": out["correct"], "check": out["check"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
