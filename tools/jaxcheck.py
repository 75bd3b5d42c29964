#!/usr/bin/env python3
"""jaxcheck — static analysis over the traced engine programs and the
source tree, plus the hot-loop primitive-budget gate (DESIGN.md §12).

Two passes:

* **jaxpr**: traces every registry scenario x program kind (serial
  runner, fleet chunk per static policy signature, streaming refill) to
  a ClosedJaxpr — nothing compiles or executes — and runs the structural
  checkers (packet-axis sort/scatter in the loop body, dtype drift,
  batched-away fast-path conds, donation aliasing, carry stability).
  Per-program watched-primitive counts are diffed against the committed
  ledger ``experiments/PRIM_BUDGET.json``.
* **ast**: lints ``src/repro/{core,api,scenarios}`` and ``benchmarks/``
  for tracer-unsafe host idioms (builtin casts on traced values,
  unseeded RNG, naked benchmark timers, ...).

Exit status is nonzero iff any error-severity finding survives.

  PYTHONPATH=src python tools/jaxcheck.py \
      --json --baseline experiments/PRIM_BUDGET.json        # the CI gate
  PYTHONPATH=src python tools/jaxcheck.py --quick           # smoke run
  PYTHONPATH=src python tools/jaxcheck.py --update-baseline # refresh
  PYTHONPATH=src python tools/jaxcheck.py --seed sort-in-loop --quick
      # falsifiability: injects a doctored program, MUST exit nonzero
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))

DEFAULT_BASELINE = "experiments/PRIM_BUDGET.json"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="jaxcheck",
        description="static analyzer + primitive-budget gate "
                    "(DESIGN.md §12)")
    ap.add_argument("--json", metavar="PATH", nargs="?", default=None,
                    const="experiments/jaxcheck.json",
                    help="write the machine-readable findings report "
                         "(default path when the flag is bare)")
    ap.add_argument("--baseline", metavar="PATH", default=None,
                    help=f"committed primitive-budget ledger to diff "
                         f"against (e.g. {DEFAULT_BASELINE})")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite --baseline (default "
                         f"{DEFAULT_BASELINE}) from the current sweep, "
                         "preserving its allowlist")
    ap.add_argument("--scenarios", nargs="+", default=None,
                    help="restrict the jaxpr sweep to these registry "
                         "scenarios (default: all)")
    ap.add_argument("--kinds", nargs="+", default=("serial", "fleet",
                                                   "refill"),
                    choices=("serial", "fleet", "refill"),
                    help="program kinds to trace")
    ap.add_argument("--max-sigs", type=int, default=None,
                    help="cap the fleet static-signature sweep (default: "
                         "every routing x traffic x placement combo)")
    ap.add_argument("--quick", action="store_true",
                    help="paper-fabric only, one fleet signature — the "
                         "fast pre-commit pass")
    ap.add_argument("--seed", metavar="RULE", default=None,
                    help="inject a doctored program violating RULE "
                         "(falsifiability check: the run must go red)")
    ap.add_argument("--no-jaxpr", action="store_true",
                    help="skip the jaxpr pass")
    ap.add_argument("--no-ast", action="store_true",
                    help="skip the AST pass")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalog and exit")
    ap.add_argument("-q", "--quiet", action="store_true",
                    help="suppress per-program progress lines")
    args = ap.parse_args(argv)

    from repro.analysis import (JAXPR_RULES, RULES, analyze, clean_trace,
                                diff_ledger, doctored_trace, iter_traces,
                                lint_tree, load_ledger, refresh_ledger,
                                save_ledger, static_sigs)
    from repro.analysis.checkers import check_donation_policy
    from repro.api import runners

    if args.list_rules:
        for rid in sorted(RULES):
            kind = "jaxpr" if rid in JAXPR_RULES else "ast"
            print(f"jaxcheck:{rid:16} [{kind}] {RULES[rid]}")
        return 0

    t0 = time.perf_counter()
    findings = []
    programs = {}
    notes = []

    scenarios, sigs = args.scenarios, None
    if args.quick:
        scenarios = scenarios or ["paper-fabric"]
        sigs = static_sigs()[:1]
    elif args.max_sigs is not None:
        sigs = static_sigs()[: args.max_sigs]
    # the missing/extra-program ledger checks only make sense when the
    # sweep covers everything the ledger covers
    full_sweep = (scenarios is None and sigs is None
                  and tuple(args.kinds) == ("serial", "fleet", "refill"))

    if not args.no_jaxpr:
        progress = (lambda s: None) if args.quiet else \
            (lambda s: print(f"  {s}", flush=True))
        traces = list(iter_traces(scenarios, sigs, kinds=args.kinds,
                                  progress=progress))
        if args.seed:
            if args.seed not in ("carry-stability",):
                traces.append(doctored_trace(args.seed))
            else:
                # two same-meta programs with different carries
                a, b = clean_trace(), clean_trace(n_packets=96)
                traces += [a, b]
        findings, programs = analyze(traces)
        findings += check_donation_policy(runners.DONATE_ARGNUMS)

        baseline_path = args.baseline or (
            DEFAULT_BASELINE if args.update_baseline else None)
        if args.update_baseline:
            if args.seed or not full_sweep:
                print("refusing --update-baseline on a partial or seeded "
                      "sweep (drop --quick/--scenarios/--kinds/--seed)")
                return 2
            old = load_ledger(ROOT / baseline_path)
            ledger = refresh_ledger(programs, old)
            save_ledger(ledger, ROOT / baseline_path)
            print(f"wrote {baseline_path} "
                  f"({len(ledger['programs'])} programs)")
        elif baseline_path:
            baseline = load_ledger(ROOT / baseline_path)
            if baseline is None:
                print(f"no baseline at {baseline_path} — run "
                      "--update-baseline to create it")
                return 2
            # the doctored program is never in the ledger; keep its
            # findings but skip the its-not-in-the-budget noise
            budget_programs = {k: v for k, v in programs.items()
                               if not k.startswith("doctored/")}
            diff_findings, notes = diff_ledger(budget_programs, baseline,
                                               full_sweep=full_sweep)
            findings += diff_findings

    if not args.no_ast:
        findings += lint_tree(ROOT)

    wall = time.perf_counter() - t0
    errors = [f for f in findings if f.severity == "error"]
    warnings = [f for f in findings if f.severity != "error"]

    for note in notes:
        print(f"note: {note}")
    for f in findings:
        print(f.render())
    print(f"jaxcheck: {len(programs)} program(s) traced, "
          f"{len(errors)} error(s), {len(warnings)} warning(s) "
          f"in {wall:.1f}s")

    if args.json:
        report = {
            "tool": "jaxcheck",
            "programs": programs,
            "notes": notes,
            "errors": [dataclasses.asdict(f) for f in errors],
            "warnings": [dataclasses.asdict(f) for f in warnings],
            "wall_s": wall,
        }
        path = ROOT / args.json
        os.makedirs(path.parent, exist_ok=True)
        with open(path, "w") as fh:
            json.dump(report, fh, indent=1)
        print(f"wrote {args.json}")

    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
