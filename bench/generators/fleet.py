"""Fleet campaigns: back-to-back ``Experiment.run_fleet`` calls, each over
one fixed workload crossed with the traffic's failure axis (outage traces
from the program's injector, one per host failure rate) and routings x
policy seeds (seeds drawn from the run's seed and the campaign index),
drained through ``width`` lanes in chunks of ``chunk_steps`` events on the
cell's chips."""
from __future__ import annotations

from harness import scenario

from .common import ROUTING, Generator as Base, SimRecord, fetch, mix, \
    sim_leaves


class Generator(Base):
    def setup(self) -> None:
        with self.rec.span("setup.build"):
            wseed = int(self.traffic.get("workload_seed", 0))
            self.base = scenario.scenario(self.cfg, wseed).build()
            self.points = self.traffic.get("failures") or []
        with self.rec.span("setup.warm"):
            self._warm()

    def _warm(self) -> None:
        """Load every program a campaign runs, with as little simulated
        work as that takes: the host-side grid of a full-size campaign
        (consts build, policy rows, one consts slice per scenario), then a
        small campaign over the failure points ``warm_points`` names (their
        packed shapes are the full grid's) with one seed more per routing
        than a cohort has lanes, so that a lane is refilled."""
        from repro.scenarios.sweep import slice_packed
        t = self.traffic
        exp, _ = self._experiment(-1, t["seeds_per_point"], self.points)
        consts, _ = exp.build()
        exp.policy_arrays()
        if len(exp.scenarios) > 1:
            for si in range(len(exp.scenarios)):
                slice_packed(consts, si)
        picked = t.get("warm_points", range(len(self.points)))
        self._campaign(-2, t["width"] + 1, [self.points[i] for i in picked])

    def _experiment(self, k: int, n_seeds: int, points):
        from repro.api import Experiment
        from repro.core.policies import PolicyConfig
        seeds = [mix(self.seed, k, i) for i in range(n_seeds)]
        pols = [(f"{name}/s{i}", PolicyConfig(routing=ROUTING[name], seed=s))
                for name in self.traffic["routings"]
                for i, s in enumerate(seeds)]
        fails = [(f"host{p['host_rate']}", scenario.failure_injector(p))
                 for p in points] or None
        return Experiment(scenarios=self.base, policies=pols,
                          failures=fails), pols

    def _campaign(self, k: int, n_seeds: int, points):
        t = self.traffic
        with self.rec.span("campaign"):
            exp, pols = self._experiment(k, n_seeds, points)
            res, stats = exp.run_fleet(width=t["width"],
                                       chunk_steps=t["chunk_steps"],
                                       devices=self.chips,
                                       return_stats=True)
            host = fetch(res.states)
        return exp, pols, host, stats

    def unit(self, k: int):
        exp, pols, host, stats = self._campaign(
            k, self.traffic["seeds_per_point"], self.points)
        steps = 0
        for s, (_, sim_setup) in enumerate(exp.scenarios):
            self.scenarios.setdefault(s, (sim_setup, sim_setup.failures))
            for p, (_, pol) in enumerate(pols):
                leaves = sim_leaves(host, s, p)
                steps += leaves["steps"]
                self.records.append(SimRecord(s, int(pol.routing),
                                              int(pol.seed), leaves))
        return {"sims": len(exp.scenarios) * len(pols), "steps": steps,
                "chunks": stats.chunks, "refills": stats.refills}
