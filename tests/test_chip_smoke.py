"""CPU rehearsal of `chip_smoke.py`, the on-chip bring-up of the main
path (StreamingDsmlService ingest -> refit -> ServingFront predict).

The script's work functions take their sizes as arguments; here they
run at tiny sizes with the Pallas kernels forced on in interpret mode,
so every check of the chip run — statistics against a HIGHEST-precision
einsum, support against the truth and against the oracle fit, served
scores against float64 products, no oracle fallback in the route
counters — runs in tier-1. The script itself must refuse the CPU.
"""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from repro.substrate import REPO_ROOT, host_device_env, run_probe

SMOKE = os.path.join(REPO_ROOT, "chip_smoke.py")
TINY = dict(m=8, p=128, s=4, n=64, chunks=6, seed=0)


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def kernels_on(monkeypatch):
    """Take the Pallas kernel path off-TPU (interpret mode)."""
    from repro.kernels import common
    monkeypatch.setattr(common, "kernels_by_default", lambda: True)


def test_chip_smoke_cpu_rehearsal(kernels_on):
    smoke = _load_smoke()
    out = smoke.run_smoke(**TINY)
    assert out["refits"] == 3
    assert out["served"] >= smoke.MIN_REQUESTS
    assert out["support"] == TINY["s"]
    assert out["stats_err"] <= 1.0 and out["beta_err"] <= smoke.BETA_TOL


def test_chip_smoke_sharded_cpu_rehearsal():
    """The --chips 4 path on four virtual CPU devices, in a child
    process (the device count must be set before jax starts)."""
    res = run_probe(
        "import importlib.util, json\n"
        f"spec = importlib.util.spec_from_file_location('s', {SMOKE!r})\n"
        "s = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(s)\n"
        "from repro.kernels import common\n"
        "common.kernels_by_default = lambda: True\n"
        f"print(json.dumps(s.run_sharded(**{TINY!r})))\n",
        n_devices=4, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["stats_err"] <= 1.0 and out["beta_err"] <= 0.02


def test_chip_smoke_refuses_cpu():
    env = host_device_env(1)
    res = subprocess.run([sys.executable, SMOKE], capture_output=True,
                         text=True, timeout=300, env=env, cwd=REPO_ROOT)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
