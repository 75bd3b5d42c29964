"""Readings for the limits of ``correct``: one short run of a cell, then,
for the same sampled simulations, the compared numbers of the program
against the plain reference (the lower readings) and of the control (the
reference with every stored float rounded to bfloat16, put in the
program's place) against the reference (the upper readings).

    python bench/tests/control.py <cell> --seed <n> [--seconds 5] [--rehearse]

Prints one JSON line: {"program": {...}, "control": {...}}.  On the chip
it runs the cell at its own size; ``--rehearse`` uses the traffic file's
rehearsal sizes on whatever JAX finds.
"""
import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    from harness import compare, core, reference

    readings = {}
    real_check = core.check

    def check(gen, cfg, picks, quantize=None):
        readings["program"] = real_check(gen, cfg, picks)
        ctl = []
        for r in picks:
            setup, fails = gen.scenarios[r.scenario_key]
            from harness import scenario
            sc = scenario.plain(setup, cfg, fails)
            ref = reference.simulate(sc, r.routing, r.seed)
            low = reference.simulate(sc, r.routing, r.seed,
                                     quantize=reference.bfloat16_round)
            ctl.append(compare.gaps(low, ref))
        readings["control"] = compare.worst(ctl)
        return readings["program"]

    core.check = check
    args = argparse.Namespace(workload=a.cell, seed=a.seed,
                              seconds=a.seconds, trace=0,
                              rehearse=a.rehearse)
    out = core.run_cell(args, time.perf_counter())
    if out is None:
        return core.EXIT_NO_DEVICE
    readings["limits"] = compare.load_limits(a.cell)
    readings["control_fails"] = not compare.judge(readings["control"],
                                                  readings["limits"])
    print(json.dumps(readings))
    return 0


if __name__ == "__main__":
    sys.exit(main())
