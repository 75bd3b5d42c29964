"""The comparison that decides ``correct``: the program's final state of a
simulation against the plain reference's, by four numbers.

- ``time_gap``: the widest gap between the program's and the reference's
  per-job done time, per-task finish time or per-packet finish time, as a
  share of the reference's makespan.  A time that one side has and the
  other lacks (a job that never finished) reads as ``MISSING`` (1e30).
- ``energy_gap``: the gap in total energy (hosts plus switches), as a share
  of the reference's.
- ``steps_gap``: the gap in event steps, as a share of the reference's.
- ``stalls``: simulations that stalled on either side (exact: limit 0).

Over several simulations each number is the worst one."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable

import numpy as np

LIMIT_DIR = Path(__file__).resolve().parents[1] / "limits"
NUMBERS = ("time_gap", "energy_gap", "steps_gap", "stalls")
# the gap of a time that one side has and the other lacks (finite, so the
# result line stays plain JSON)
MISSING = 1e30


def _gap(prog: np.ndarray, ref: np.ndarray) -> float:
    prog = np.asarray(prog, np.float64)[: ref.shape[0]]
    both = np.isnan(prog) & np.isnan(ref)
    one = np.isnan(prog) ^ np.isnan(ref)
    if one.any():
        return MISSING
    d = np.abs(np.where(both, 0.0, prog - np.where(both, 0.0, ref)))
    return float(d.max()) if d.size else 0.0


def gaps(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The four numbers for one simulation (``prog`` holds the program's
    leaves, see ``generators.common.sim_leaves``)."""
    span = float(np.nanmax(ref["job_done_t"])) if np.isfinite(
        ref["job_done_t"]).any() else 1.0
    t = max(_gap(prog[k], ref[k])
            for k in ("job_done_t", "task_finish", "pkt_finish"))
    return {
        "time_gap": t / span,
        "energy_gap": abs(float(prog["energy_j"]) - ref["energy_j"])
        / max(ref["energy_j"], 1e-30),
        "steps_gap": abs(int(prog["steps"]) - ref["steps"])
        / max(ref["steps"], 1),
        "stalls": int(bool(prog["stalled"]) or bool(ref["stalled"])),
    }


def worst(per_sim: Iterable[Dict[str, float]]) -> Dict[str, float]:
    out = {k: 0.0 for k in NUMBERS}
    for g in per_sim:
        for k in ("time_gap", "energy_gap", "steps_gap"):
            out[k] = max(out[k], g[k])
        out["stalls"] += g["stalls"]
    return out


def load_limits(workload: str) -> Dict[str, float]:
    with open(LIMIT_DIR / f"{workload}.json") as f:
        spec = json.load(f)
    return {k: float(spec[k]["limit"]) for k in NUMBERS}


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(numbers[k] <= limits[k] for k in NUMBERS)
