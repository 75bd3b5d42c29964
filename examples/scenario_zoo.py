"""Tour of the scenario library: build each registered scenario, print its
fabric shape and route diversity, then race SDN vs legacy routing on every
topology in one packed ``repro.api.Experiment`` (DESIGN.md §5, §6).

  PYTHONPATH=src python examples/scenario_zoo.py                # all fabrics
  PYTHONPATH=src python examples/scenario_zoo.py fat-tree leaf-spine
"""
import sys

import numpy as np

from repro.api import Experiment
from repro.core import PolicyConfig, ROUTE_LEGACY, ROUTE_SDN
from repro.scenarios import get_scenario, list_scenarios
from repro.util import enable_compile_cache

enable_compile_cache()

names = sys.argv[1:] or list_scenarios()
scens = []
for name in names:
    sc = get_scenario(name)
    setup = sc.build()
    topo = setup.cluster.topo
    hosts = np.arange(topo.n_hosts)
    host_pairs = setup.route_table.n_cand_between(hosts[:, None],
                                                  hosts[None, :])
    off_diag = host_pairs[~np.eye(topo.n_hosts, dtype=bool)]
    print(f"{sc.name:22} {topo.n_hosts:3d} hosts {topo.n_switches:3d} switches "
          f"{topo.n_links:4d} links   host-pair route diversity: "
          f"min {off_diag.min()}  max {off_diag.max()}  "
          f"mean {off_diag.mean():.1f}   [{sc.description}]")
    scens.append((sc.name, setup))

res = Experiment(
    scenarios=scens,
    policies=[("sdn", PolicyConfig(routing=ROUTE_SDN, job_concurrency=2)),
              ("legacy", PolicyConfig(routing=ROUTE_LEGACY,
                                      job_concurrency=2))]).run()
print()
rows = res.rows()
for sdn, leg in zip(rows[::2], rows[1::2]):
    gain = (leg["mean_completion_s"] - sdn["mean_completion_s"]) \
        / leg["mean_completion_s"] * 100
    print(f"{sdn['scenario']:22} completion sdn {sdn['mean_completion_s']:7.1f}s "
          f"legacy {leg['mean_completion_s']:7.1f}s   sdn gain {gain:+5.1f}%")
print("\nscenario zoo OK")
