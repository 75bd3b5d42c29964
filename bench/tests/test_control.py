"""The control — the plain reference in bfloat16, put in the program's
place — must come out not correct, while the program on the same sampled
simulations comes out correct (rehearsal sizes, CPU)."""
import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT
from test_rehearsal import CELLS, _chips


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_program_passes(cell):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    if _chips(cell) > 1:
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                            f"{_chips(cell)}")
    p = subprocess.run([sys.executable, "bench/tests/control.py", cell,
                        "--seed", "31337", "--seconds", "1", "--rehearse"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["control_fails"], out
    lim = out["limits"]
    assert all(out["program"][k] <= lim[k] for k in lim), out
