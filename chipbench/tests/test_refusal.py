"""The benchmark refuses to measure without a chip, and without the
program beside it; either way it prints no result."""
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import harness
from chipbench.tests.conftest import ROOT


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "tenants.refit",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_the_cpu():
    res = _run(ROOT)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "TPU" in res.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = _run(tmp_path)
    assert res.returncode != 0
    assert res.stdout.strip() == ""


def test_an_unknown_device_kind_has_no_peaks():
    assert harness.device_peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(SystemExit, match="no peaks"):
        harness.device_peaks("cpu")
