"""Each cell's traffic driven through ``bench/run.py`` at its tiny
rehearsal sizes on the CPU: the whole path runs and the check passes, but
no result line is printed and the exit code is not 0 — a CPU run never
reports device metrics."""
import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT

CELLS = [c["name"] for c in json.load(open(ROOT / "BENCHMARK.json"))[
    "workloads"]]


def _run(cell, *extra, devices=1):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    if devices > 1:
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                            f"{devices}")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell,
         "--seed", "4294967311", "--seconds", "1", "--trace", "0", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


def _chips(cell):
    bench = json.load(open(ROOT / "BENCHMARK.json"))
    return next(c["chips"] for c in bench["workloads"] if c["name"] == cell)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_runs_and_reports_nothing(cell):
    p = _run(cell, "--rehearse", devices=_chips(cell))
    assert p.returncode == 3, p.stderr[-3000:]
    assert p.stdout.strip() == "", p.stdout
    assert "correct=True" in p.stderr, p.stderr[-3000:]
    assert "compiles in window 0" in p.stderr


@pytest.mark.parametrize("cell", CELLS)
def test_refuses_without_accelerator(cell):
    p = _run(cell, devices=_chips(cell))
    assert p.returncode == 3
    assert p.stdout.strip() == ""
    assert "accelerator" in p.stderr
