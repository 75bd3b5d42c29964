"""Quickstart: the paper's experiment through the unified Experiment API
(DESIGN.md §6) + a tiny LM train run.

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.api import Experiment, PolicyConfig
from repro.core import ROUTE_LEGACY, ROUTE_SDN
from repro.scenarios import get_scenario
from repro.util import enable_compile_cache

enable_compile_cache()

# --- 1. BigDataSDNSim: SDN vs legacy on the paper's fat-tree (Tables 2-3).
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

# --- 2. Train a small LM with the same repo's training stack
import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.data import TokenPipeline
from repro.models import get_model
from repro.train import AdamWConfig, make_train_step
from repro.train import init as opt_init

cfg = get_smoke_config("qwen3-4b")
api = get_model(cfg)
params = api.init(jax.random.PRNGKey(0))
ocfg = AdamWConfig(total_steps=30, warmup_steps=3)
opt = opt_init(ocfg, params)
step = jax.jit(make_train_step(api, ocfg))
pipe = TokenPipeline(vocab=cfg.vocab, batch=8, seq=32)
for i in range(30):
    batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(i).items()}
    params, opt, met = step(params, opt, batch)
    if i % 10 == 0 or i == 29:
        print(f"step {i:3d}  loss {float(met['loss']):.3f}  "
              f"lr {float(met['lr']):.2e}")
print("quickstart OK")
