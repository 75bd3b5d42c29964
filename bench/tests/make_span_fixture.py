"""Cut a small fixture for ``test_program_trace.py`` out of a recorded
trace: the host ``bench.*`` and ``repro.*`` spans and every device's
``XLA Ops`` events, each with its op_name cut after the engine phase it
names, inside a short slice that starts ``--before`` ms before the first
start (or end, with ``--edge end``) of the span ``--at`` in the window;
the window span is cut to the slice.

    python bench/tests/make_span_fixture.py <trace.xplane.pb> <out.json.gz> \
        --at repro.run.dispatch [--edge start|end] [--before 0.5] [--ms 3]
"""
import argparse
import gzip
import json
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def cut_op_name(op_name: str) -> str:
    """The op_name up to and including the component that names its
    phase (the part the reduction reads), or its first 60 characters
    where it names none."""
    from harness.program_trace import op_phase
    for m in re.finditer(r"[^/;]+", op_name):
        if op_phase(m.group(0)):
            return op_name[:m.end()]
    return op_name[:60]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("xplane")
    ap.add_argument("out")
    ap.add_argument("--at", required=True)
    ap.add_argument("--edge", choices=("start", "end"), default="start")
    ap.add_argument("--before", type=float, default=0.5)
    ap.add_argument("--ms", type=float, default=3.0)
    a = ap.parse_args()
    from harness import program_trace as pt
    from harness import trace
    planes = pt.load_planes(a.xplane)
    host = [(p, l) for p, l in planes if p.startswith("/host:")]
    start = min(s for _, l in host for evs in l.values()
                for n, s, _ in evs if n in (trace.WINDOW, trace.OPEN))
    at = min((s, e) for _, l in host for evs in l.values()
             for n, s, e in evs if n == a.at and s >= start)
    w0 = at[a.edge == "end"] - a.before * 1e6
    w1 = w0 + a.ms * 1e6
    out = []
    for p, lines in planes:
        if p.startswith("/host:"):
            evs = [(n, max(s, w0), min(e, w1)) for l in lines.values()
                   for n, s, e in l
                   if n not in (trace.OPEN, trace.WINDOW)
                   and e > w0 and s < w1]
            evs.append((trace.WINDOW, w0, w1))
            out.append((p, {"python": evs}))
        elif p.startswith("/device:") and "XLA Ops" in lines:
            evs = [(n[:40], s, e, cut_op_name(o))
                   for n, s, e, o in lines["XLA Ops"] if e > w0 and s < w1]
            out.append((p, {"XLA Ops": evs}))
    with gzip.open(a.out, "wt") as f:
        json.dump(out, f)
    print(f"{a.out}: {sum(len(v) for _, l in out for v in l.values())} "
          "events")
    return 0


if __name__ == "__main__":
    sys.exit(main())
