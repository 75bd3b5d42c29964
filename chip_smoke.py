#!/usr/bin/env python3
"""Drive the simulator's main path on the TPU, through ``repro.api``.

    python chip_smoke.py              # one chip, five phases
    python chip_smoke.py --chips 4    # run_fleet over four chips vs one

Each phase prints one line per run: ``compile_s`` (first call minus second
call), ``wall_s`` (the second call, synced with ``block_until_ready``), the
engine's ``steps`` and the simulated outputs.  The phases:

* ``device``: platform, kind and count.  Without a TPU the script exits
  non-zero; it never falls back to the CPU.
* ``paper``: paper-fabric, SDN vs legacy, through ``Experiment.run``, with
  the same run on the host CPU in this process as the reference: rows must
  agree within ``PAPER_RTOL``/``PAPER_STEPS_ATOL`` and SDN must beat legacy
  on both devices.
* ``xl-serial``: leaf-spine-xl through ``Experiment.run``, one policy at a
  time, run to completion: not stalled, finite completion and energy.
* ``xl-fleet``: leaf-spine-xl through ``run_fleet(devices=1)``, more sims
  than lanes so lanes retire and refill, bitwise equal to
  ``Experiment.run`` of each cell of the same grid.
* ``stream``: ``run_stream`` on leaf-spine with Poisson arrivals, 2
  policies x 32 slots, long enough to refill: every load retires, the
  windowed metrics are finite.

``--chips 4`` runs only ``run_fleet(devices=4)`` on a leaf-spine-xl seed
grid and compares it bitwise with ``run_fleet(devices=1)``.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
A failed phase raises, so no such line is printed and the exit code is not
0.  Everything runs in this one process, which holds the chip.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# the paper phase's reference runs on the host CPU backend, so keep it
# available when the platforms are pinned (the accelerator stays first)
_plats = os.environ.get("JAX_PLATFORMS")
if _plats and "cpu" not in _plats.split(","):
    os.environ["JAX_PLATFORMS"] = _plats + ",cpu"

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import Experiment, PolicyConfig  # noqa: E402
from repro.core import ROUTE_LEGACY, ROUTE_SDN  # noqa: E402
from repro.scenarios.registry import stream_arrivals  # noqa: E402
from repro.util import enable_compile_cache  # noqa: E402

POLICIES = [("sdn", PolicyConfig(routing=ROUTE_SDN)),
            ("legacy", PolicyConfig(routing=ROUTE_LEGACY))]
PAPER_METRICS = ("mean_completion_s", "mean_transmission_s", "energy_kwh")
# chip vs host CPU on the paper rows: TPU and XLA-CPU round f32 division
# and reductions differently, so the outputs agree to float precision, not
# bit for bit (what the chip showed is recorded in CHANGES.md)
PAPER_RTOL = 1e-3
PAPER_STEPS_ATOL = 0

XL = "leaf-spine-xl"
FLEET_SEEDS = 8        # sims in the fleet grid ...
FLEET_WIDTH = 4        # ... drained through this many lanes
FLEET_CHUNK = 64
STREAM = dict(scenario="leaf-spine", rate=0.1, horizon=1000.0, slots=32,
              chunk_steps=128, job_concurrency=4)


def log(phase: str, **fields) -> None:
    body = " ".join(f"{k}={_fmt(v)}" for k, v in fields.items())
    print(f"[{phase}] {body}", flush=True)


def _fmt(v) -> str:
    if isinstance(v, float):
        return repr(v)
    if isinstance(v, dict):
        return "{" + ",".join(f"{k}:{_fmt(x)}" for k, x in v.items()) + "}"
    return str(v)


def twice(fn, sync=lambda out: out):
    """-> (second output, compile_s, wall_s, first output).  ``sync`` picks
    the device arrays to wait on, so the clock stops when the work is
    done, not when it is enqueued."""
    t0 = time.perf_counter()
    first = fn()
    jax.block_until_ready(sync(first))
    t1 = time.perf_counter()
    second = fn()
    jax.block_until_ready(sync(second))
    t2 = time.perf_counter()
    return second, (t1 - t0) - (t2 - t1), t2 - t1, first


def differing_leaves(a, b) -> dict:
    """{SimState leaf: largest absolute difference over finite entries}
    for every leaf of two equal-shape state grids that is not bit-equal
    (NaN == NaN)."""
    out = {}
    for name, la, lb in zip(a._fields, a, b):
        la, lb = np.asarray(la), np.asarray(lb)
        if not np.array_equal(la, lb, equal_nan=True):
            fin = np.isfinite(la) & np.isfinite(lb)
            out[name] = float(np.max(np.abs(
                la[fin].astype(np.float64) - lb[fin].astype(np.float64)),
                initial=0.0))
    return out


def assert_same_states(a, b, what: str) -> None:
    """Leaf-by-leaf bit equality (NaN == NaN) of two SimState grids."""
    differ = differing_leaves(a, b)
    if differ:
        raise AssertionError(f"{what}: SimState leaves differ (largest "
                             f"absolute difference): {differ}")


def _states(res):
    return res.states


def _column(states, p: int):
    """Policy column ``p`` of a one-scenario [1, P] state grid, shaped like
    the [1, 1] grid ``Experiment.run`` returns for that cell alone."""
    return type(states)(*(np.asarray(a)[:, p:p + 1] for a in states))


def check_device(chips: int):
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"no TPU: jax.devices()[0] is {d.platform} "
                         f"({d.device_kind}); this script does not fall "
                         "back to the CPU")
    if len(devs) < chips:
        raise SystemExit(f"--chips {chips} needs {chips} devices, JAX "
                         f"sees {len(devs)}")
    log("device", platform=d.platform, kind=d.device_kind, count=len(devs))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def _rel(a: float, b: float) -> float:
    if a == b:
        return 0.0
    return abs(a - b) / max(abs(a), abs(b))


def _sdn_beats_legacy(rows) -> bool:
    by = {r["policy"]: r for r in rows}
    return all(by["sdn"][m] < by["legacy"][m] for m in PAPER_METRICS)


def phase_paper(scenario: str = "paper-fabric") -> None:
    exp = Experiment(scenario, policies=POLICIES)
    res, compile_s, wall_s, first = twice(exp.run, _states)
    assert_same_states(first.states, res.states,
                       "paper: first vs second call")
    rows = res.rows()
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        ref = Experiment(scenario, policies=POLICIES).run()
        ref_rows = ref.rows()
    ran_on = {d.platform for d in res.states.time.devices()}
    ref_on = {d.platform for d in ref.states.time.devices()}
    diff = {m: max(_rel(r[m], q[m]) for r, q in zip(rows, ref_rows))
            for m in PAPER_METRICS}
    steps_diff = max(abs(r["steps"] - q["steps"])
                     for r, q in zip(rows, ref_rows))
    differ = differing_leaves(res.states, ref.states)
    bitwise = not differ
    for r, q in zip(rows, ref_rows):
        log("paper", policy=r["policy"], compile_s=compile_s, wall_s=wall_s,
            steps=r["steps"], cpu_steps=q["steps"],
            **{m: r[m] for m in PAPER_METRICS},
            **{f"cpu_{m}": q[m] for m in PAPER_METRICS})
    log("paper", ran_on=sorted(ran_on), reference_on=sorted(ref_on),
        max_rel_diff=diff, steps_abs_diff=steps_diff,
        bitwise_equal_to_cpu=bitwise, differing_leaves_max_abs=differ,
        rtol=PAPER_RTOL, steps_atol=PAPER_STEPS_ATOL)
    if ref_on != {"cpu"}:
        raise AssertionError(f"paper: reference ran on {ref_on}, not cpu")
    if not (_sdn_beats_legacy(rows) and _sdn_beats_legacy(ref_rows)):
        raise AssertionError("paper: SDN no longer beats legacy")
    bad = {m: d for m, d in diff.items() if not d <= PAPER_RTOL}
    if bad or steps_diff > PAPER_STEPS_ATOL:
        raise AssertionError(f"paper: chip vs cpu beyond tolerance: {bad}, "
                             f"steps differ by {steps_diff}")


def _check_row(phase: str, row) -> None:
    if row["stalled"]:
        raise AssertionError(f"{phase}: {row['policy']} stalled")
    for m in ("mean_completion_s", "energy_kwh", "makespan_s"):
        if not math.isfinite(row[m]) or row[m] <= 0:
            raise AssertionError(f"{phase}: {row['policy']} {m}={row[m]}")


def phase_serial(scenario: str = XL) -> None:
    for name, pol in POLICIES:
        exp = Experiment(scenario, policies=[(name, pol)])
        res, compile_s, wall_s, first = twice(exp.run, _states)
        assert_same_states(first.states, res.states,
                           f"xl-serial/{name}: first vs second")
        row = res.rows()[0]
        log("xl-serial", policy=name, compile_s=compile_s, wall_s=wall_s,
            steps=row["steps"], mean_completion_s=row["mean_completion_s"],
            energy_kwh=row["energy_kwh"], makespan_s=row["makespan_s"],
            stalled=row["stalled"])
        _check_row("xl-serial", row)


def _fleet_exp(scenario: str, seeds: int) -> Experiment:
    # legacy routing pins each flow by a seeded hash, so the seeds give
    # trajectories of different lengths: lanes retire at different times
    return Experiment(scenario, policies=POLICIES[1:], seeds=range(seeds))


def phase_fleet(scenario: str = XL, seeds: int = FLEET_SEEDS,
                width: int = FLEET_WIDTH) -> None:
    exp = _fleet_exp(scenario, seeds)
    (res, stats), compile_s, wall_s, (first, _) = twice(
        lambda: exp.run_fleet(width=width, chunk_steps=FLEET_CHUNK,
                              devices=1, return_stats=True),
        lambda out: out[0].states)
    assert_same_states(first.states, res.states,
                       "xl-fleet: first vs second call")
    # the reference is Experiment.run of every grid cell on its own (the
    # serial runner): one vmapped run of the whole grid runs both sides of
    # every batched cond for the longest lane, and at this size takes
    # minutes (ROADMAP A1), while the cells share one compiled program
    t0 = time.perf_counter()
    differ = {}
    for p, (name, pol) in enumerate(exp.policies):
        cell = Experiment(scenario, policies=[(name, pol)]).run()
        d = differing_leaves(_column(res.states, p), cell.states)
        if d:
            differ[name] = d
    ref_s = time.perf_counter() - t0
    steps = np.asarray(res.states.steps)[0]
    rows = res.rows()
    log("xl-fleet", sims=stats.sims, width=stats.width, devices=stats.devices,
        chunks=stats.chunks, refills=stats.refills, compile_s=compile_s,
        wall_s=wall_s, reference_s_incl_compile=ref_s,
        steps=f"{int(steps.min())}..{int(steps.max())}",
        mean_completion_s=float(np.mean([r["mean_completion_s"]
                                         for r in rows])),
        bitwise_equal_to_run=not differ, differing_leaves_max_abs=differ)
    if differ:
        raise AssertionError("xl-fleet: run_fleet differs from "
                             f"Experiment.run: {differ}")
    if stats.refills <= 0:
        raise AssertionError("xl-fleet: no lane was refilled")
    for r in rows:
        _check_row("xl-fleet", r)


def phase_stream(scenario: str = STREAM["scenario"],
                 horizon: float = STREAM["horizon"],
                 slots: int = STREAM["slots"]) -> None:
    conc = STREAM["job_concurrency"]
    exp = Experiment(scenario, policies=[
        (n, PolicyConfig(routing=p.routing, job_concurrency=conc))
        for n, p in POLICIES])
    res, compile_s, wall_s, first = twice(
        lambda: exp.run_stream(stream_arrivals(rate=STREAM["rate"], seed=0),
                               horizon, slots=slots,
                               chunk_steps=STREAM["chunk_steps"]),
        lambda out: out.jobs)
    st = res.stats
    for pi, name in enumerate(res.policy_names):
        if not np.array_equal(first.jobs[pi]["t_done"],
                              res.jobs[pi]["t_done"]):
            raise AssertionError(f"stream/{name}: first vs second differ")
        sm = res.summary(pi)
        w = res.windows(pi)
        live = w["n_done"] > 0
        finite = all(np.all(np.isfinite(w[k][live])) for k in (
            "throughput_jobs_s", "p50_sojourn_s", "p99_sojourn_s",
            "utilization", "energy_j"))
        log("stream", policy=name, compile_s=compile_s, wall_s=wall_s,
            trace_len=st.trace_len, slots=st.slots, loads=st.loads,
            retired=st.retired, refills=st.refills, chunks=st.chunks,
            windows=int(w["t0"].size),
            throughput_jobs_s=sm["throughput_jobs_s"],
            p50_sojourn_s=sm["p50_sojourn_s"],
            p99_sojourn_s=sm["p99_sojourn_s"], windows_finite=finite)
        if not finite:
            raise AssertionError(f"stream/{name}: non-finite window")
    if not (st.loads == st.retired == st.trace_len * st.lanes):
        raise AssertionError(f"stream: loads {st.loads} retired "
                             f"{st.retired} trace {st.trace_len}")
    if st.refills <= 0:
        raise AssertionError("stream: the ring never refilled")


def phase_fleet_chips(chips: int, scenario: str = XL,
                      seeds: int = FLEET_SEEDS,
                      width: int = FLEET_WIDTH) -> None:
    exp = _fleet_exp(scenario, seeds)
    out = {}
    for n in (1, chips):
        (res, stats), compile_s, wall_s, (first, _) = twice(
            lambda: exp.run_fleet(width=width, chunk_steps=FLEET_CHUNK,
                                  devices=n, return_stats=True),
            lambda o: o[0].states)
        assert_same_states(first.states, res.states,
                           f"fleet x{n}: first vs second")
        steps = np.asarray(res.states.steps)[0]
        log(f"fleet-x{n}", sims=stats.sims, width=stats.width,
            devices=stats.devices, chunks=stats.chunks,
            refills=stats.refills, compile_s=compile_s, wall_s=wall_s,
            steps=f"{int(steps.min())}..{int(steps.max())}")
        if stats.devices != n:
            raise AssertionError(f"fleet ran on {stats.devices} devices, "
                                 f"asked for {n}")
        out[n] = res
    assert_same_states(out[1].states, out[chips].states,
                       f"run_fleet devices={chips} vs devices=1")
    log(f"fleet-x{chips}", bitwise_equal_to_devices_1=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the fleet sharded over four chips, "
                         "compared with one")
    args = ap.parse_args(argv)
    cache = enable_compile_cache()
    device = check_device(args.chips)
    log("cache", dir=cache or os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    if args.chips == 1:
        phase_paper()
        phase_serial()
        phase_fleet()
        phase_stream()
    else:
        phase_fleet_chips(args.chips)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
