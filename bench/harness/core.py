"""One run of one cell: set-up, the measured window, the trace, the check
against the plain reference, and the result line.

Everything that belongs to a cell is found by name: its configuration in
``configs/``, its traffic mix in ``traffic/`` (a data file naming the
generator in ``generators/`` that reads it), each metric's reader in
``metrics/`` and the limits of the comparison in ``limits/``."""
from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from . import compare, reference, scenario, trace
from .spans import Recorder, listen_compiles

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
CACHE = BENCH / ".cache"

# a run that finds no chip, or fewer than the cell asks for
EXIT_NO_DEVICE = 3


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class GcPause:
    """The time Python's garbage collector holds the window, from its own
    callbacks: a stall of the host shows here if the collector caused it."""

    def __init__(self):
        self.count, self.seconds, self._t0 = 0, 0.0, 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, _info) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.count += 1
            self.seconds += time.perf_counter() - self._t0

    def close(self) -> None:
        gc.callbacks.remove(self._on)


def load_cell(workload: str):
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; one of "
                         f"{sorted(cells)}")
    return bench, cells[workload]


def load_traffic(name: str) -> dict:
    with open(BENCH / "traffic" / f"{name}.json") as f:
        return json.load(f)


def metric_reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell: str, traced: bool) -> List[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics, or
    with ``traced`` the per-layer metrics that name it (or, naming no
    cells, move one of its end-to-end metrics)."""
    def in_cell(m):
        return cell in m["workloads"] if "workloads" in m else True
    e2e = [m for m in bench["end_to_end"] if in_cell(m)]
    if not traced:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def configure_jax():
    """The persistent compile cache at a fixed path in the checkout, for
    every program however short its compile."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)  # no eviction
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()
    return jax


def device_info(jax, n: int) -> Dict:
    devs = jax.devices()[:n]
    peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
             for d in devs if d.memory_stats()]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": int(max(peaks)) if peaks else 0}


def pick_checks(records, n: int, seed: int):
    """The simulations the reference redoes: the longest (most event
    steps) and ``n - 1`` more drawn from the seed."""
    if not records:
        return []
    longest = max(range(len(records)), key=lambda i: records[i].leaves[
        "steps"])
    rest = [i for i in range(len(records)) if i != longest]
    rng = np.random.default_rng(seed & 0xFFFFFFFFFFFF)
    extra = rng.choice(len(rest), size=min(n - 1, len(rest)),
                       replace=False) if n > 1 and rest else []
    return [records[longest]] + [records[rest[i]] for i in extra]


def check(gen, cfg: dict, picks, quantize=None) -> Dict[str, float]:
    plains, routes, per_sim = {}, {}, []
    for r in picks:
        if r.scenario_key not in plains:
            setup, fails = gen.scenarios[r.scenario_key]
            plains[r.scenario_key] = scenario.plain(setup, cfg, fails)
        sc = plains[r.scenario_key]
        key = (sc["n_nodes"], tuple(sc["link_src"]), tuple(sc["link_dst"]))
        if key not in routes:
            routes[key] = reference.Routes(sc["n_nodes"], sc["link_src"],
                                           sc["link_dst"], sc["k_max"])
        ref = reference.simulate(sc, r.routing, r.seed, quantize=quantize,
                                 routes=routes[key])
        per_sim.append(compare.gaps(r.leaves, ref))
    return compare.worst(per_sim)


def apply_rehearsal(cfg: dict, traffic: dict) -> None:
    """Tiny sizes for a CPU rehearsal (``rehearsal`` in the traffic file)."""
    over = traffic.get("rehearsal", {})
    cfg["scenario"] = {**cfg["scenario"], **over.get("scenario", {})}
    traffic.update(over.get("traffic", {}))


def run(args, t_start: float) -> int:
    """Run the cell and print its result line; the exit code."""
    out = run_cell(args, t_start)
    if out is None:
        return EXIT_NO_DEVICE
    for k, v in out["check"].items():
        log(f"check {k} {v['value']!r} limit {v['limit']!r}")
    if args.rehearse:
        log(f"rehearsal on {out['device']['platform']}: "
            f"correct={out['correct']}; no result is reported without an "
            "accelerator")
        return EXIT_NO_DEVICE
    print(json.dumps(out), flush=True)
    return 0


def run_cell(args, t_start: float) -> Optional[Dict]:
    """One run of the cell: the result (``check`` last), or None where JAX
    finds no accelerator or fewer chips than the cell asks for (unless
    ``args.rehearse``)."""
    bench, cell = load_cell(args.workload)
    cfg = scenario.load_config(cell["config"])
    traffic = load_traffic(cell["traffic"])
    if args.rehearse:
        apply_rehearsal(cfg, traffic)
    jax = configure_jax()
    devs = jax.devices()
    if not args.rehearse and (devs[0].platform == "cpu"
                              or len(devs) < cell["chips"]):
        log(f"needs {cell['chips']} accelerator chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")
        return None
    sys.path.insert(0, str(ROOT / "src"))
    from repro.api import runners

    rec = Recorder()
    listen_compiles(rec)
    mod = importlib.import_module(f"generators.{traffic['generator']}")
    gen = mod.Generator(cfg, traffic, args.seed, cell["chips"], rec)
    gen.setup()
    setup_compile_s = sum(d for _, d in rec.compile_events)
    n_compiles, n_traces = len(rec.compile_events), runners.trace_count()

    trace_dir = CACHE / "trace" / args.workload
    trace_units = int(traffic.get("trace_units", 1)) if args.trace else 0
    if trace_units:
        shutil.rmtree(trace_dir, ignore_errors=True)
    units, gen.records = [], []
    session = None
    t_trace = time.perf_counter()
    if trace_units:
        session = trace.Session(jax, str(trace_dir),
                                traffic.get("trace_seconds"))
    gc_pause = GcPause()
    w0 = time.perf_counter()
    setup_s = t_trace - t_start
    paused = 0.0
    k = 0
    while k == 0 or time.perf_counter() - w0 - paused < args.seconds:
        t0 = time.perf_counter()
        info = gen.unit(k)
        info["wall_s"] = time.perf_counter() - t0
        # a unit the timer cut short was not traced whole
        info["traced"] = session is not None and session.active
        units.append(info)
        k += 1
        if session is not None and k == trace_units:
            p0 = time.perf_counter()
            session.stop()
            session = None
            paused += time.perf_counter() - p0
    w1 = time.perf_counter() - paused
    gc_pause.close()
    compiles_in_window = len(rec.compile_events) - n_compiles
    traces_in_window = runners.trace_count() - n_traces
    device = device_info(jax, cell["chips"])
    records = gen.records
    gen.release()

    tr = None
    if trace_units:
        path = trace.find_xplane(str(trace_dir))
        tr = trace.reduce_file(path) if path else None
        if tr:
            for name, busy in tr["busy_by_device"].items():
                log(f"trace: {name} busy {busy!r} s of {tr['window_s']!r} s")
            device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
    log(f"window: {len(units)} units, {sum(u['sims'] for u in units)} sims "
        f"in {w1 - w0!r} s; compiles in window {compiles_in_window}, "
        f"engine traces in window {traces_in_window}")
    log(f"window: unit walls {[u['wall_s'] for u in units]!r} s; "
        f"{gc_pause.count} garbage collections in {gc_pause.seconds!r} s")

    picks = pick_checks(records, int(traffic["check_sims"]), args.seed)
    t_check = time.perf_counter()
    numbers = check(gen, cfg, picks)
    log(f"check: {len(picks)} of {len(records)} sims against the reference "
        f"in {time.perf_counter() - t_check:.1f} s")
    limits = compare.load_limits(args.workload)
    correct = bool(picks) and compare.judge(numbers, limits)

    ctx = {"cell": cell, "traffic": traffic, "rec": rec, "units": units,
           "trace": tr, "setup_s": setup_s, "setup_compile_s":
           setup_compile_s, "w0": w0, "w1": w1}
    metrics = {}
    for m in cell_metrics(bench, args.workload, bool(args.trace)):
        v = metric_reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    failed = sum(not r.finished for r in records)
    out = {"correct": correct, "attempted": len(records), "failed": failed,
           "metrics": metrics, "device": device}
    if tr:
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    out["check"] = {k: {"value": numbers[k], "limit": limits[k]}
                    for k in compare.NUMBERS}
    return out
