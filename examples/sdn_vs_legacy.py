"""Full reproduction of the paper's §5 use-case (Figs. 11a/b, 12a/b, 13).

  PYTHONPATH=src python examples/sdn_vs_legacy.py [--full]

Prints per-job tables for both network modes and the three headline
deltas, plus the calibration grid over the paper's under-specified
parameters (packet split, AM admission width).
"""
import argparse
import sys

import numpy as np

sys.path.insert(0, ".")
from benchmarks.fig11_13_usecase import main as bench_main  # noqa: E402
from repro.util import enable_compile_cache  # noqa: E402


def run(full: bool):
    report = bench_main(quick=not full)
    fd = report["fig_data"]
    print("\nPer-job detail (best-match calibration, jobs sorted by size):")
    order = np.argsort(fd["sdn_completion"])
    print(f"{'job':>4} {'tr SDN':>9} {'tr LEG':>9} {'ct SDN':>9} "
          f"{'ct LEG':>9} {'map SDN':>9} {'map LEG':>9}")
    for j in order:
        print(f"{j:4d} {fd['sdn_transmission'][j]:9.1f} "
              f"{fd['legacy_transmission'][j]:9.1f} "
              f"{fd['sdn_completion'][j]:9.1f} "
              f"{fd['legacy_completion'][j]:9.1f} "
              f"{fd['sdn_map_exec'][j]:9.1f} "
              f"{fd['legacy_map_exec'][j]:9.1f}")
    he, se = fd["sdn_energy"]
    hel, sel = fd["legacy_energy"]
    print(f"\nEnergy (Fig. 13): SDN hosts {he / 3.6e6:.2f} kWh + switches "
          f"{se / 3.6e6:.2f} kWh; legacy hosts {hel / 3.6e6:.2f} + "
          f"switches {sel / 3.6e6:.2f} kWh")
    print(f"\nHeadline deltas vs paper (41/24/22%): "
          f"{report['best_match_pct']}")


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    run(ap.parse_args().full)
