"""Run one cell of the benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell (``BENCHMARK.json``) names its
configuration and traffic mix; the run builds and warms them (set-up),
runs whole units of the traffic back to back for ``--seconds`` (the
window), checks a sample of the simulations the window completed against
the plain reference, and prints one JSON line: ``correct``, ``attempted``,
``failed``, ``metrics`` (end-to-end, or per-layer with ``--trace 1``),
``device`` and the compared numbers with their limits (``check``).  It
needs a TPU: without one it exits with code 3 and prints no result.
``--rehearse`` runs the same path at the traffic file's tiny rehearsal
sizes on whatever JAX finds, and still prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    # the TPU runtime logs to a fixed path under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    from harness import core
    return core.run(args, T_START)


if __name__ == "__main__":
    sys.exit(main())
