"""Reduction of a JAX profiler trace (``*.xplane.pb``) to device metrics.

- The traced window is the host span ``bench.window`` (the benchmark
  opens it around the traced units); where the session was stopped by
  time inside a unit, from the marker ``bench.window_open`` to the end of
  the profiler session.
- A device is a plane named ``/device:<KIND>:<n>``; its operations are the
  events of its ``XLA Ops`` line.  Busy time is the union of those
  intervals inside the window; the idle share is 1 - busy / window.
- ``device_ops``: the operations that took most device time of their own
  (nested operations' time taken out), summed by name and averaged over
  the devices.
- ``idle_gaps``: the device's idle time inside the window, summed by the
  innermost benchmark span (``bench.*``) open on the host at the middle of
  each gap (``between`` where none was), averaged over the devices.
"""
from __future__ import annotations

import glob
import re
import threading
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import numpy as np

_DEVICE = re.compile(r"^/device:([A-Za-z]+):(\d+)$")
WINDOW = "bench.window"
OPEN = "bench.window_open"
SESSION = "/session"


class Session:
    """The profiler session of a traced run: started before the window,
    stopped by ``stop()`` after the traced units or, with ``seconds``, by
    a timer, whichever comes first."""

    def __init__(self, jax, path: str, seconds=None):
        self.jax, self._lock, self.active = jax, threading.Lock(), True
        jax.profiler.start_trace(path)
        with jax.profiler.TraceAnnotation(OPEN):
            pass
        self.span = jax.profiler.TraceAnnotation(WINDOW)
        self.span.__enter__()
        self.timer = None
        if seconds:
            self.timer = threading.Timer(float(seconds), self._stop_trace)
            self.timer.daemon = True
            self.timer.start()

    def _stop_trace(self):
        with self._lock:
            if self.active:
                self.active = False
                self.jax.profiler.stop_trace()

    def stop(self):
        """From the thread that started the session."""
        if self.timer is not None:
            self.timer.cancel()
        with self._lock:
            if self.active:
                self.span.__exit__(None, None, None)
        self._stop_trace()


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None


def union(starts: np.ndarray, ends: np.ndarray) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals of possibly overlapping ones."""
    if starts.size == 0:
        return []
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, s.size - 1)
    return list(zip(s[first].tolist(), reach[last].tolist()))


def self_times(starts: np.ndarray, ends: np.ndarray, names) -> Dict:
    """Time of each operation name less the time of the operations nested
    inside it (a while loop's body ops run inside the loop's own event);
    names are cut to the HLO instruction name (``%fusion.12``)."""
    acc: Dict[str, float] = defaultdict(float)
    stack: List[list] = []                     # [end, name, self time]
    for i in np.lexsort((-ends, starts)):
        s, e = starts[i], ends[i]
        while stack and stack[-1][0] <= s:
            _, n, t = stack.pop()
            acc[n] += t
        if stack and e <= stack[-1][0]:
            stack[-1][2] -= e - s
        stack.append([e, names[i].split(" = ")[0], e - s])
    for _, n, t in stack:
        acc[n] += t
    return acc


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def reduce_planes(planes) -> Optional[Dict]:
    """``planes``: iterable of (name, {line name: [(event name, start_ns,
    end_ns)]}).  Returns None where the trace holds no window or no device
    operation."""
    host_spans: List[Tuple[str, float, float]] = []
    devices: Dict[str, Tuple[np.ndarray, np.ndarray, List[str]]] = {}
    for pname, lines in planes:
        if pname.startswith("/host:"):
            for evs in lines.values():
                host_spans += [(n, a, b) for n, a, b in evs
                               if n.startswith("bench.")]
        elif _DEVICE.match(pname) and "XLA Ops" in lines:
            evs = lines["XLA Ops"]
            devices[pname] = (np.array([a for _, a, _ in evs], np.float64),
                              np.array([b for _, _, b in evs], np.float64),
                              [n for n, _, _ in evs])
    windows = [(a, b) for n, a, b in host_spans if n == WINDOW]
    if not windows:
        opened = [a for n, a, _ in host_spans if n == OPEN]
        ends = [b for p, lines in planes if p == SESSION
                for _, _, b in lines.get("span", [])]
        windows = [(opened[0], ends[0])] if opened and ends else []
    if not windows or not devices:
        return None
    w0, w1 = windows[0]
    window_s = (w1 - w0) * 1e-9
    inner = sorted(((n[len("bench."):], a, b) for n, a, b in host_spans
                    if n != WINDOW), key=lambda x: x[2] - x[1])
    per_dev, op_time = {}, defaultdict(float)
    idle_by = defaultdict(float)
    for pname, (st, en, names) in sorted(devices.items()):
        busy = _clip(union(st, en), w0, w1)
        per_dev[pname] = sum(b - a for a, b in busy) * 1e-9
        keep = np.flatnonzero((en > w0) & (st < w1))
        for n, d in self_times(np.maximum(st[keep], w0),
                               np.minimum(en[keep], w1),
                               [names[i] for i in keep]).items():
            op_time[n] += d * 1e-9
        edges = np.array([w0] + [x for iv in busy for x in iv] + [w1])
        ga, gb = edges[0::2], edges[1::2]
        mid, length = 0.5 * (ga + gb), np.maximum(gb - ga, 0.0)
        free = np.ones(mid.size, bool)
        for n, s, e in inner:                      # innermost span first
            hit = free & (mid >= s) & (mid < e)
            idle_by[n] += float(length[hit].sum()) * 1e-9
            free &= ~hit
        idle_by["between"] += float(length[free].sum()) * 1e-9
    busy_s = float(np.mean(list(per_dev.values())))
    n_dev = len(per_dev)                     # per device, as busy_s is
    op_time = {n: v / n_dev for n, v in op_time.items()}
    idle_by = {n: v / n_dev for n, v in idle_by.items()}
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(((n, v) for n, v in idle_by.items() if v > 0),
                  key=lambda kv: -kv[1])[:10]
    return {"window_s": window_s, "busy_s": busy_s,
            "busy_by_device": per_dev,
            "device_ops": [[n, float(v)] for n, v in ops],
            "idle_gaps": [[n, float(v)] for n, v in gaps]}


def load_planes(path: str):
    """(plane name, {line name: [(event, start_ns, end_ns)]}) of a trace."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for pl in pd.planes:
        stats = dict(pl.stats)
        if "profile_stop_time" in stats:       # event times are relative
            out.append((SESSION, {"span": [(
                "session", 0, stats["profile_stop_time"]
                - stats["profile_start_time"])]}))
        lines = {}
        for ln in pl.lines:
            lines[ln.name] = [(e.name, e.start_ns, e.end_ns)
                              for e in ln.events]
        out.append((pl.name, lines))
    return out


def reduce_file(path: str) -> Optional[Dict]:
    return reduce_planes(load_planes(path))
