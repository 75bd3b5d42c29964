"""What every traffic generator shares: seed mixing, the leaves of a final
state that the comparison reads, and the record of each simulation run in
the window."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Hashable, List

import numpy as np

LEAVES = ("job_done_t", "task_finish", "pkt_finish", "host_energy",
          "switch_energy", "steps", "stalled")
ROUTING = {"legacy": 0, "sdn": 1}


def mix(*parts: int) -> int:
    """A 31-bit seed from whole numbers of any size (the same parts give
    the same seed)."""
    words = []
    for p in parts:
        p = int(p)
        words += [p & 0xFFFFFFFF, (p >> 32) & 0xFFFFFFFF, int(p < 0)]
    return int(np.random.SeedSequence(words).generate_state(1)[0]
               & 0x7FFFFFFF)


def fetch(states) -> Dict[str, np.ndarray]:
    """The compared leaves of a ``[S, P, ...]`` state grid, on the host
    (waits for the device)."""
    import jax
    got = jax.device_get(tuple(getattr(states, k) for k in LEAVES))
    return dict(zip(LEAVES, (np.asarray(a) for a in got)))


def sim_leaves(host: Dict[str, np.ndarray], s: int, p: int) -> Dict:
    return {
        "job_done_t": host["job_done_t"][s, p],
        "task_finish": host["task_finish"][s, p],
        "pkt_finish": host["pkt_finish"][s, p],
        "energy_j": float(np.sum(host["host_energy"][s, p], dtype=np.float64)
                          + np.sum(host["switch_energy"][s, p],
                                   dtype=np.float64)),
        "steps": int(host["steps"][s, p]),
        "stalled": bool(host["stalled"][s, p]),
    }


@dataclasses.dataclass
class SimRecord:
    """One simulation the window completed: what the reference needs to
    redo it (``scenario_key`` names an entry of the generator's ``scenarios``)
    and the program's leaves."""

    scenario_key: Hashable
    routing: int
    seed: int
    leaves: Dict[str, Any]

    @property
    def finished(self) -> bool:
        return (not self.leaves["stalled"]
                and bool(np.isfinite(self.leaves["job_done_t"]).all()))


class Generator:
    """Base of a traffic gen.  ``setup`` builds and warms what the
    cell's traffic uses; ``unit(k)`` runs the k-th unit of the closed loop
    (a call or a campaign) to completion and returns its counts;
    ``scenarios`` maps each ``SimRecord.scenario_key`` to ``(SimSetup,
    outage schedule or None)`` for the reference."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, chips: int,
                 rec):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.chips, self.rec = chips, rec
        self.records: List[SimRecord] = []
        self.scenarios: Dict[Hashable, Any] = {}

    def setup(self) -> None:
        raise NotImplementedError

    def unit(self, k: int) -> Dict[str, float]:
        raise NotImplementedError

    def release(self) -> None:
        """Drop what the program holds on the device."""
        from repro.api import runners
        runners.cache_clear()
