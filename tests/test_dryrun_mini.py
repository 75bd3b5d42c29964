"""Dry-run machinery on a miniature mesh in a subprocess (the 512-device
flag must not leak into this test process)."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import dataclasses
import jax
from repro.configs import SHAPES, get_smoke_config
from repro.launch.dryrun import lower_one
from repro.launch.mesh import make_mesh
from repro.roofline.terms import raw_counts

results = {}
mesh = make_mesh((2, 4), ("data", "model"))
shape = dataclasses.replace(SHAPES["train_4k"], seq_len=64, global_batch=8)
for arch in ["qwen3-4b", "qwen3-moe-30b-a3b", "falcon-mamba-7b",
             "jamba-v0.1-52b", "whisper-base", "qwen2-vl-72b"]:
    cfg = get_smoke_config(arch)
    compiled = lower_one(cfg, shape, mesh, backend="chunked", remat=True,
                         microbatch=0)
    rc = raw_counts(compiled, chips=8)
    mem = compiled.memory_analysis()
    results[arch] = {"flops": rc["flops"], "wire": rc["wire_bytes"],
                     "temp": getattr(mem, "temp_size_in_bytes", 0)}
# decode shape too (TP path)
dshape = dataclasses.replace(SHAPES["decode_32k"], seq_len=64,
                             global_batch=8)
cfg = get_smoke_config("qwen3-4b")
compiled = lower_one(cfg, dshape, mesh, backend="chunked", remat=True,
                     microbatch=0)
results["qwen3-4b-decode"] = {"ok": True}
print("RESULT " + json.dumps(results))
"""


@pytest.mark.slow
def test_mini_dryrun_all_families():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"   # the child forces 8 host devices
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("RESULT ")][0]
    results = json.loads(line[len("RESULT "):])
    assert len(results) == 7
    for arch, r in results.items():
        if "flops" in r:
            assert r["flops"] > 0, arch
