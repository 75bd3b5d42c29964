"""What the program itself records in a JAX profiler trace, reduced to
per-layer numbers; the same ``*.xplane.pb`` that ``trace.py`` reduces.

- Program spans: ``jax.profiler.TraceAnnotation`` host spans named
  ``repro.*`` (front door, runner dispatch, fleet chunk boundaries).  They
  share the device's clock, so ``idle_gaps`` here gives the device's idle
  time inside the window by the innermost span of either prefix,
  ``bench.*`` or ``repro.*``, open at each instant (``between`` where none
  was; ``untraced_tail`` after the last recorded span where the session
  was cut inside a unit), averaged over the devices.  Unlike
  ``trace.py``, which gives each gap whole to the span open at its
  middle, a gap that spans several host spans is split among them.
- Engine phases: ``core/engine._step`` names its phases with
  ``jax.named_scope``; XLA keeps the scope in each operation's
  ``op_name`` (``.../while/body/rates/...``, or ``vmap(rates)`` where the
  step is vmapped inside the loop).  An operation belongs to the first
  phase its op_name names; ``device_phases`` is device self time in the
  window (nested operations' time taken out, as ``trace.self_times``)
  by phase, ``unscoped`` for operations that name none, averaged over
  the devices.

A program that records neither (one older than the spans) reduces to no
spans and all time ``unscoped``; the readers then return None.
"""
from __future__ import annotations

import re
import warnings
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import numpy as np

from . import trace

PHASES = ("admit_place", "activate", "chaos", "rates", "advance", "complete")
UNSCOPED = "unscoped"
PREFIXES = ("bench.", "repro.")
BETWEEN = "between"
# where a timer stopped the session inside a unit (no closed window span),
# the host's spans stop being recorded before the device's ops and the
# session's end: idle time after the last recorded span is the session's
# stop, not host work outside every span
UNTRACED = "untraced_tail"
# the stat of an XLA op's event metadata that holds the HLO op_name
OP_NAME_STAT = "tf_op"

_SCOPE = re.compile(r"^(?:(?:vmap|jvp|transpose)\()*([A-Za-z_]+)\)*$")


def op_phase(op_name: str) -> str:
    """The engine phase an op_name names (its first path component that
    is a phase, inside transform wrappers such as ``vmap(...)``), or ''."""
    for comp in re.split(r"[/;]", op_name or ""):
        m = _SCOPE.match(comp)
        if m and m.group(1) in PHASES:
            return m.group(1)
    return ""


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, i: int, end: int):
    """(field number, value) of each field of the protobuf message in
    ``buf[i:end]``: an int for a varint, (start, end) for a
    length-delimited field, None for a fixed-width one."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def op_names(path: str) -> Dict[str, Dict[str, str]]:
    """{device plane: {XLA op event name: op_name}} from the event
    metadata of each device plane of an XSpace file (``tf_op``, which the
    profiler fills from the HLO op's metadata).  ``ProfileData`` gives
    events but not their metadata's stats, so this reads the fields it
    needs off the protobuf wire format: XSpace.planes (1); XPlane.name
    (2), event_metadata (4), stat_metadata (5); XEventMetadata.name (2),
    stats (5); XStatMetadata.name (2); XStat.metadata_id (1), str_value
    (5), ref_value (7); a map entry's key (1) and value (2)."""
    with open(path, "rb") as f:
        buf = f.read()

    def text(v):
        return bytes(buf[v[0]:v[1]]).decode("utf-8", "replace")

    def entries(ranges):
        for r in ranges:
            yield dict(_fields(buf, *r)).get(2)

    out = {}
    for field, plane in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        name, events, stats = "", [], []
        for pf, v in _fields(buf, *plane):
            if pf == 2:
                name = text(v)
            elif pf == 4:
                events.append(v)
            elif pf == 5:
                stats.append(v)
        if not trace._DEVICE.match(name):
            continue
        stat_names = {}
        for md in entries(stats):
            d = dict(_fields(buf, *md)) if md else {}
            stat_names[d.get(1)] = text(d[2]) if 2 in d else ""
        ops = {}
        for md in entries(events):
            ev_name, op = "", ""
            for ef, v in _fields(buf, *md) if md else ():
                if ef == 2:
                    ev_name = text(v)
                elif ef == 5:
                    st = dict(_fields(buf, *v))
                    if stat_names.get(st.get(1)) != OP_NAME_STAT:
                        continue
                    op = (text(st[5]) if 5 in st
                          else stat_names.get(st.get(7), ""))
            if op and ev_name not in ops:
                ops[ev_name] = op.rstrip(":")
        out[name] = ops
    return out


def load_planes(path: str):
    """(plane name, {line name: events}) of a trace: on host planes the
    ``bench.*`` and ``repro.*`` spans as (name, start_ns, end_ns); on
    device planes the ``XLA Ops`` events as (name, start_ns, end_ns,
    op_name); and ``trace.SESSION``'s span where the session's stop time
    is recorded."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    names = op_names(path)
    out = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for pl in pd.planes:
            stats = dict(pl.stats)
            if "profile_stop_time" in stats:
                out.append((trace.SESSION, {"span": [(
                    "session", 0, stats["profile_stop_time"]
                    - stats["profile_start_time"])]}))
            lines = {}
            if pl.name.startswith("/host:"):
                for ln in pl.lines:
                    lines[ln.name] = [(e.name, e.start_ns, e.end_ns)
                                      for e in ln.events
                                      if e.name.startswith(PREFIXES)]
            elif trace._DEVICE.match(pl.name):
                ops = names.get(pl.name, {})
                for ln in pl.lines:
                    if ln.name == "XLA Ops":
                        lines[ln.name] = [
                            (e.name, e.start_ns, e.end_ns,
                             ops.get(e.name, "")) for e in ln.events]
            out.append((pl.name, lines))
    return out


def _window(host_spans, planes) -> Optional[Tuple[float, float]]:
    """The traced window, as ``trace.reduce_planes`` finds it."""
    windows = [(a, b) for n, a, b in host_spans if n == trace.WINDOW]
    if windows:
        return windows[0]
    opened = [a for n, a, _ in host_spans if n == trace.OPEN]
    ends = [b for p, lines in planes if p == trace.SESSION
            for _, _, b in lines.get("span", [])]
    return (opened[0], ends[0]) if opened and ends else None


def _idle_by_span(busy, w0: float, w1: float, inner) -> Dict[str, float]:
    """The idle time of [w0, w1) outside the merged ``busy`` intervals,
    by the innermost of the ``inner`` spans (sorted shortest first) open
    at each instant: the window is cut at every span edge, and each piece
    goes whole to the innermost span open in it."""
    cuts = np.unique(np.clip(np.array(
        [w0, w1] + [x for _, a, b in inner for x in (a, b)], np.float64),
        w0, w1))
    a = np.array([x for x, _ in busy], np.float64)
    b = np.array([x for _, x in busy], np.float64)
    before = np.concatenate([[0.0], np.cumsum(b - a)[:-1]])
    # busy time before each cut, by interpolation over the busy edges
    done = (np.interp(cuts, np.ravel(np.column_stack([a, b])),
                      np.ravel(np.column_stack([before, before + b - a])))
            if busy else np.zeros(cuts.size))
    idle = np.diff((cuts - w0) - done)
    mid = 0.5 * (cuts[:-1] + cuts[1:])
    out: Dict[str, float] = defaultdict(float)
    free = np.ones(mid.size, bool)
    for n, s, e in inner:                          # innermost span first
        hit = free & (mid >= s) & (mid < e)
        out[n] += float(idle[hit].sum())
        free &= ~hit
    out[BETWEEN] += float(idle[free].sum())
    return out


def reduce_planes(planes) -> Optional[Dict]:
    """``planes`` as ``load_planes`` gives them.  None where the trace
    holds no window or no device operation."""
    host_spans: List[Tuple[str, float, float]] = []
    devices = {}
    phase_of: Dict[str, str] = {}           # op_name -> phase, per trace

    def phase(op_name: str) -> str:
        if op_name not in phase_of:
            phase_of[op_name] = op_phase(op_name) or UNSCOPED
        return phase_of[op_name]

    for pname, lines in planes:
        if pname.startswith("/host:"):
            for evs in lines.values():
                host_spans += [(n, a, b) for n, a, b in evs
                               if n.startswith(PREFIXES)]
        elif trace._DEVICE.match(pname) and lines.get("XLA Ops"):
            evs = lines["XLA Ops"]
            devices[pname] = (np.array([e[1] for e in evs], np.float64),
                              np.array([e[2] for e in evs], np.float64),
                              [phase(e[3]) for e in evs])
    win = _window(host_spans, planes)
    if win is None or not devices:
        return None
    w0, w1 = win
    inner = sorted(((n, a, b) for n, a, b in host_spans
                    if n not in (trace.WINDOW, trace.OPEN)),
                   key=lambda x: x[2] - x[1])
    if inner and not any(n == trace.WINDOW for n, _, _ in host_spans):
        inner.append((UNTRACED, max(b for _, _, b in inner), w1))
    phases: Dict[str, float] = defaultdict(float)
    idle_by: Dict[str, float] = defaultdict(float)
    busy = []
    for _, (st, en, ph) in sorted(devices.items()):
        iv = trace._clip(trace.union(st, en), w0, w1)
        busy.append(sum(b - a for a, b in iv) * 1e-9)
        keep = np.flatnonzero((en > w0) & (st < w1))
        for n, d in trace.self_times(np.maximum(st[keep], w0),
                                     np.minimum(en[keep], w1),
                                     [ph[i] for i in keep]).items():
            phases[n] += d * 1e-9
        for n, v in _idle_by_span(iv, w0, w1, inner).items():
            idle_by[n] += v * 1e-9
    n_dev = len(devices)
    phases = {n: phases.get(n, 0.0) / n_dev for n in PHASES + (UNSCOPED,)}
    gaps = sorted(((n, v / n_dev) for n, v in idle_by.items() if v > 0),
                  key=lambda kv: -kv[1])
    spans = [(n, a, b) for n, a, b in host_spans if b > w0 and a < w1]
    return {"window_s": (w1 - w0) * 1e-9, "busy_s": float(np.mean(busy)),
            "device_phases": phases, "idle_gaps": gaps, "spans": spans}


def reduce_file(path: str) -> Optional[Dict]:
    return reduce_planes(load_planes(path))


def from_ctx(ctx) -> Optional[Dict]:
    """The reduction of the run's trace (read once per run, kept in
    ``ctx``); None in an untraced run or where the trace reduces to
    nothing."""
    if "program_trace" not in ctx:
        ctx["program_trace"] = None
        if ctx.get("trace"):
            from . import core
            path = trace.find_xplane(
                str(core.CACHE / "trace" / ctx["cell"]["name"]))
            pt = reduce_file(path) if path else None
            if pt:
                core.log(f"program trace: device_phases "
                         f"{pt['device_phases']!r}")
                core.log(f"program trace: idle_gaps "
                         f"{[[n, v] for n, v in pt['idle_gaps'][:12]]!r}")
            ctx["program_trace"] = pt
    return ctx["program_trace"]


def has_phases(pt: Optional[Dict]) -> bool:
    return bool(pt) and any(pt["device_phases"][p] > 0 for p in PHASES)


def phase_us(ctx, phase: str):
    """Device self time (us) of one engine phase per engine step, over
    the traced units (the base of ``readers.step_device_us``)."""
    pt = from_ctx(ctx)
    steps = sum(u["steps"] for u in ctx["units"] if u["traced"])
    if not has_phases(pt) or steps <= 0:
        return None
    return 1e6 * pt["device_phases"][phase] / steps


def span_ms_per(ctx, name: str, per: str):
    """Median over the ``per`` spans in the window (``bench.call``) of
    the summed duration (ms) of the ``name`` spans inside each."""
    pt = from_ctx(ctx)
    if not pt:
        return None
    inside = [(a, b) for n, a, b in pt["spans"] if n == name]
    if not inside:
        return None
    totals = [sum(b - a for a, b in inside if a >= p0 and b <= p1)
              for n, p0, p1 in pt["spans"] if n == per]
    totals = [t for t in totals if t > 0]
    return 1e-6 * float(np.median(totals)) if totals else None


def idle_share_in(ctx, prefix: str):
    """Per cent of the traced window in which the device idled while the
    innermost open span was one named ``prefix*``."""
    pt = from_ctx(ctx)
    if not pt or pt["window_s"] <= 0 or not any(
            n.startswith(prefix) for n, _, _ in pt["spans"]):
        return None
    idle = sum(v for n, v in pt["idle_gaps"] if n.startswith(prefix))
    return 100.0 * idle / pt["window_s"]
