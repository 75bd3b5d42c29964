"""step_fail_us.grid: device self microseconds per engine step of the
operations whose op_name holds a ``fail_transitions`` component (the
engine's failure transitions, inside ``chaos``: task and packet reverts
on a new host or link death), over the traced window; the same window
and self-time rule as the phase readers.  None where no operation names
that scope.

The session stops inside the campaign (``trace_seconds``), so the
campaign is not traced whole and its engine steps are counted in
proportion: the traced units' steps times the share of their chunks
whose ``repro.fleet.chunk`` span starts inside the window.  Each
operation's op_name is read from its own event metadata, by id: the
programs of a campaign (one chunk per cohort signature, init, refill)
name their instructions independently."""
import re

import numpy as np

from harness import core, program_trace, trace

_SCOPE = re.compile(r"^(?:(?:vmap|jvp|transpose)\()*fail_transitions\)*$")


def in_scope(op_name: str) -> bool:
    return any(_SCOPE.match(c) for c in re.split(r"[/;]", op_name or ""))


def event_op_names(path: str):
    """{device plane: [op_name of each ``XLA Ops`` event, in file order]},
    each from the ``tf_op`` stat of the event's own metadata entry.
    XSpace.planes (1); XPlane.name (2), lines (3), event_metadata (4),
    stat_metadata (5); a map entry's key (1) and value (2);
    XEventMetadata.stats (5); XStatMetadata.name (2); XLine.name (2),
    events (4); XEvent.metadata_id (1); XStat.metadata_id (1),
    str_value (5), ref_value (7)."""
    fields = program_trace._fields
    with open(path, "rb") as f:
        buf = f.read()

    def text(v):
        return bytes(buf[v[0]:v[1]]).decode("utf-8", "replace")

    out = {}
    for field, plane in fields(buf, 0, len(buf)):
        if field != 1:
            continue
        parts = {2: [], 3: [], 4: [], 5: []}
        for pf, v in fields(buf, *plane):
            if pf in parts:
                parts[pf].append(v)
        name = text(parts[2][0]) if parts[2] else ""
        if not trace._DEVICE.match(name):
            continue
        stat_names = {}
        for entry in parts[5]:
            md = dict(fields(buf, *entry)).get(2)
            d = dict(fields(buf, *md)) if md else {}
            stat_names[d.get(1)] = text(d[2]) if 2 in d else ""
        ops = {}
        for entry in parts[4]:
            e = dict(fields(buf, *entry))
            op = ""
            for ef, v in fields(buf, *e[2]) if 2 in e else ():
                if ef != 5:
                    continue
                st = dict(fields(buf, *v))
                if stat_names.get(st.get(1)) == program_trace.OP_NAME_STAT:
                    op = (text(st[5]) if 5 in st
                          else stat_names.get(st.get(7), ""))
            ops[e.get(1)] = op.rstrip(":")
        for line in parts[3]:
            lf = list(fields(buf, *line))
            if next((text(v) for f, v in lf if f == 2), "") != "XLA Ops":
                continue
            out[name] = [ops.get(dict(fields(buf, *v)).get(1), "")
                         for f, v in lf if f == 4]
    return out


def load_planes(path: str):
    """``program_trace.load_planes`` with each device event's op_name
    taken from its own metadata (``event_op_names``)."""
    names = event_op_names(path)
    out = []
    for p, lines in program_trace.load_planes(path):
        evs = lines.get("XLA Ops") if trace._DEVICE.match(p) else None
        if evs is not None:
            ops = names.get(p, [])
            if len(ops) != len(evs):
                raise ValueError(f"{p}: {len(ops)} op names for "
                                 f"{len(evs)} events")
            lines = {**lines, "XLA Ops": [e[:3] + (op,)
                                          for e, op in zip(evs, ops)]}
        out.append((p, lines))
    return out


def fail_us(planes, steps: int):
    """Microseconds per step under ``fail_transitions`` in ``planes`` (as
    ``load_planes`` gives them), mean over the devices."""
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
        labels = ["fail" if in_scope(evs[i][3]) else "other" for i in keep]
        named |= "fail" in labels
        total += trace.self_times(np.maximum(st[keep], w0),
                                  np.minimum(en[keep], w1),
                                  labels).get("fail", 0.0)
    return 1e-3 * total / n_dev / steps if named else None


def window_steps(planes, steps: int, chunks: int) -> float:
    """The engine steps of the traced window: ``steps`` of the traced
    units in proportion to their ``chunks`` whose ``repro.fleet.chunk``
    span starts inside the window."""
    host = [s for p, lines in planes if p.startswith("/host:")
            for evs in lines.values() for s in evs]
    win = program_trace._window(host, planes)
    if win is None or chunks <= 0:
        return 0.0
    inside = sum(1 for n, a, _ in host
                 if n == "repro.fleet.chunk" and win[0] <= a < win[1])
    return steps * min(inside, chunks) / chunks


def read(ctx):
    if not ctx.get("trace"):
        return None
    path = trace.find_xplane(str(core.CACHE / "trace" / ctx["cell"]["name"]))
    if not path:
        return None
    units = ctx["units"][:int(ctx["traffic"].get("trace_units", 1))]
    planes = load_planes(path)
    steps = window_steps(planes, sum(u["steps"] for u in units),
                         sum(u.get("chunks", 0) for u in units))
    return fail_us(planes, steps)
