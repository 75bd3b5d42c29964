"""Cut a small fixture for ``test_trace.py`` out of a recorded trace: the
host ``bench.*`` spans and every device's ``XLA Ops`` events inside a short
slice of the traced window, starting half a millisecond before the first
device operation, with the window span cut to that slice.

    python bench/tests/make_trace_fixture.py <trace.xplane.pb> <out.json.gz> [--ms 3]
"""
import argparse
import gzip
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("xplane")
    ap.add_argument("out")
    ap.add_argument("--ms", type=float, default=3.0)
    a = ap.parse_args()
    from harness import trace
    planes = trace.load_planes(a.xplane)
    host = [(p, l) for p, l in planes if p.startswith("/host:")]
    start = min(s for _, l in host for evs in l.values()
                for n, s, _ in evs if n in (trace.WINDOW, trace.OPEN))
    w0 = min(s for p, lines in planes if p.startswith("/device:")
             for _, s, _ in lines.get("XLA Ops", []) if s >= start) - 0.5e6
    w1 = w0 + a.ms * 1e6
    out = []
    for p, lines in planes:
        if p.startswith("/host:"):
            evs = [(n, max(s, w0), min(e, w1)) for l in lines.values()
                   for n, s, e in l
                   if n.startswith("bench.")
                   and n not in (trace.OPEN, trace.WINDOW)
                   and e > w0 and s < w1]
            evs.append((trace.WINDOW, w0, w1))
            out.append((p, {"python": evs}))
        elif p.startswith("/device:") and "XLA Ops" in lines:
            evs = [(n[:40], s, e) for n, s, e in lines["XLA Ops"]
                   if e > w0 and s < w1]
            out.append((p, {"XLA Ops": evs}))
    with gzip.open(a.out, "wt") as f:
        json.dump(out, f)
    print(f"{a.out}: {sum(len(v) for _, l in out for v in l.values())} "
          "events")
    return 0


if __name__ == "__main__":
    sys.exit(main())
