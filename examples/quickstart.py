"""Quickstart: the paper's experiment through the unified Experiment API
(DESIGN.md §6).

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.api import Experiment, PolicyConfig
from repro.core import ROUTE_LEGACY, ROUTE_SDN
from repro.scenarios import get_scenario
from repro.util import enable_compile_cache

enable_compile_cache()

# BigDataSDNSim: SDN vs legacy on the paper's fat-tree (Tables 2-3).
# One declarative experiment; .run() compiles once and returns the grid.
res = Experiment(
    scenarios=get_scenario("paper-fabric", n_each=5),   # the 15-job mix
    policies=[("SDN", PolicyConfig(routing=ROUTE_SDN, job_concurrency=2)),
              ("legacy", PolicyConfig(routing=ROUTE_LEGACY,
                                      job_concurrency=2))]).run()
jr = res.job_report()
for pi, (name, row) in enumerate(zip(res.policy_names, res.rows())):
    print(f"{name:7s} mean job transmission "
          f"{np.nanmean(jr['transmission_time'][0, pi]):7.1f} s   "
          f"completion {row['mean_completion_s']:7.1f} s   "
          f"energy {row['energy_kwh']:6.2f} kWh")

print("quickstart OK")
