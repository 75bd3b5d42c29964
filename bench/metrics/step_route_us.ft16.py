"""step_route_us.ft16: device self microseconds per engine step of the
operations whose op_name holds a ``route_choice`` component (the engine's
candidate choice and route-link composition, inside ``activate``), over
the traced campaign; the same window and self-time rule as the phase
readers.  None where no operation names that scope."""
import re

import numpy as np

from harness import core, program_trace, trace

_SCOPE = re.compile(r"^(?:(?:vmap|jvp|transpose)\()*route_choice\)*$")


def in_scope(op_name: str) -> bool:
    return any(_SCOPE.match(c) for c in re.split(r"[/;]", op_name or ""))


def route_us(planes, steps: int):
    """Microseconds per step under ``route_choice`` in ``planes`` (as
    ``program_trace.load_planes`` gives them), mean over the devices."""
    host = [s for p, lines in planes if p.startswith("/host:")
            for evs in lines.values() for s in evs]
    win = program_trace._window(host, planes)
    if win is None or steps <= 0:
        return None
    w0, w1 = win
    total, n_dev, named = 0.0, 0, False
    for p, lines in planes:
        evs = lines.get("XLA Ops") if trace._DEVICE.match(p) else None
        if not evs:
            continue
        n_dev += 1
        st = np.array([e[1] for e in evs], np.float64)
        en = np.array([e[2] for e in evs], np.float64)
        keep = np.flatnonzero((en > w0) & (st < w1))
        labels = ["route" if in_scope(evs[i][3]) else "other" for i in keep]
        named |= "route" in labels
        total += trace.self_times(np.maximum(st[keep], w0),
                                  np.minimum(en[keep], w1),
                                  labels).get("route", 0.0)
    return 1e-3 * total / n_dev / steps if named else None


def read(ctx):
    steps = sum(u["steps"] for u in ctx["units"] if u["traced"])
    if not ctx.get("trace"):
        return None
    path = trace.find_xplane(str(core.CACHE / "trace" / ctx["cell"]["name"]))
    return route_us(program_trace.load_planes(path), steps) if path else None
