"""Sweep calls: back-to-back ``Experiment.run`` calls, each over a freshly
built workload (seed drawn from the run's seed and the call index) and the
traffic's routings as one policy batch (the ``policy_batch`` runner), the
way an interactive user explores a design.  Each call pays the front door:
scenario build, route enumeration and consts."""
from __future__ import annotations

from harness import scenario

from .common import ROUTING, Generator as Base, SimRecord, fetch, mix, \
    sim_leaves


class Generator(Base):
    def setup(self) -> None:
        # the registry's seed must vary the workload without changing its
        # shapes, or every call would compile a new program
        if not self.cfg["workload_seed_keeps_shapes"]:
            raise ValueError(f"{self.cfg['name']}: a fresh workload per "
                             "call would change its shapes")
        with self.rec.span("setup.warm"):
            self._call(-1)

    def _call(self, k: int):
        from repro.api import Experiment
        from repro.core.policies import PolicyConfig
        wseed, pseed = mix(self.seed, k, 1), mix(self.seed, k, 2)
        pols = [(name, PolicyConfig(routing=ROUTING[name], seed=pseed))
                for name in self.traffic["routings"]]
        with self.rec.span("call"):
            with self.rec.span("build"):
                exp = Experiment(scenarios=scenario.scenario(self.cfg, wseed),
                                 policies=pols)
                exp.build()
            host = fetch(exp.run().states)
        return exp, wseed, pseed, host

    def unit(self, k: int):
        exp, wseed, pseed, host = self._call(k)
        self.scenarios[wseed] = (exp.scenarios[0][1], None)
        steps = 0
        for p, name in enumerate(self.traffic["routings"]):
            leaves = sim_leaves(host, 0, p)
            steps += leaves["steps"]
            self.records.append(SimRecord(wseed, ROUTING[name], pseed,
                                          leaves))
        return {"sims": len(self.traffic["routings"]), "steps": steps}
