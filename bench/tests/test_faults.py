"""With a fault planted in the program under test, a run drives the rest
of its path (rehearsal sizes, CPU) and ``correct`` comes out false."""
import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT

CASES = [
    ("frozen_step", "paper-sweep", 1),
    ("frozen_step", "paper-fleet", 1),
    ("half_batch", "paper-sweep", 1),
    ("half_batch", "paper-fleet", 1),
    ("altered", "paper-sweep", 1),
    ("altered", "paper-fleet", 1),
]


@pytest.mark.parametrize("fault,cell,devices", CASES)
def test_fault_is_not_correct(fault, cell, devices):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    if devices > 1:
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                            f"{devices}")
    p = subprocess.run([sys.executable, "bench/tests/faults.py", fault, cell],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is False, out
