"""Expert-parallel MoE (shard_map all-to-all) vs dense oracle, incl. grads.

Runs in a subprocess with 8 forced host devices (same isolation pattern
as test_dryrun_mini)."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_smoke_config
from repro.models.moe import moe_apply, moe_init

cfg = dataclasses.replace(get_smoke_config("qwen3-moe-30b-a3b"),
                          capacity_factor=8.0)   # no drops -> exact match
mesh = jax.make_mesh((2, 4), ("data", "model"))
key = jax.random.PRNGKey(0)
p = moe_init(key, cfg)
x = jax.random.normal(key, (8, 16, cfg.d_model), jnp.float32).astype(cfg.dtype)

ref_out, ref_aux = moe_apply(p, x, cfg)          # no mesh -> dense path

def loss(p_, x_):
    o, aux = moe_apply(p_, x_, cfg)
    return jnp.sum(o.astype(jnp.float32) ** 2) + aux

g_ref = jax.grad(loss)(p, x)
w_spec = {"router": P(None, None), "wi": P("model", None, None),
          "wg": P("model", None, None), "wo": P("model", None, None)}
p_sh = {k: NamedSharding(mesh, v) for k, v in w_spec.items()}
x_sh = NamedSharding(mesh, P(("data", "model"), None, None))
with jax.set_mesh(mesh):
    out_ep, _ = jax.jit(lambda p_, x_: moe_apply(p_, x_, cfg),
                        in_shardings=(p_sh, x_sh))(p, x)
    g_ep = jax.jit(jax.grad(loss), in_shardings=(p_sh, x_sh))(p, x)
fwd_err = float(np.max(np.abs(np.asarray(out_ep, np.float32)
                              - np.asarray(ref_out, np.float32))))
grad_errs = {}
for kk in ("wi", "wg", "wo", "router"):
    a = np.asarray(g_ep[kk], np.float32); b = np.asarray(g_ref[kk], np.float32)
    grad_errs[kk] = float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9))
print("RESULT " + json.dumps({"fwd_err": fwd_err, "grad_errs": grad_errs}))
"""


@pytest.mark.slow
def test_ep_moe_matches_dense_including_grads():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"   # the child forces 8 host devices
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("RESULT ")][0]
    r = json.loads(line[len("RESULT "):])
    assert r["fwd_err"] < 2e-2, r
    for kk, v in r["grad_errs"].items():
        assert v < 5e-2, (kk, r)
