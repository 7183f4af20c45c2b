"""Shared set-up of the benchmark's CPU tests.

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests

They are run explicitly: the repository's own test run collects only
`tests/`. The program lives under `src/`, the benchmark at the root.
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for path in (ROOT, os.path.join(ROOT, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)


def _configs():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    out = {}
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            out[c["name"]] = json.load(f)
    return out


CONFIGS = _configs()

# peaks of the chip the tests stand for ("TPU v5 lite" in peaks.json),
# passed explicitly where the device is the CPU
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}

# the tiny size every cell runs at here: 8 tasks, 128 features, 64-row
# chunks, a refit every 128 rows, 3 pool chunks, 100 predicts a second
TINY = dict(m=8, p=128, chunk_n=64)


def tiny_spec(cell: str):
    """(benchmark, cell entry, config, traffic) of `cell`, cut to TINY."""
    import copy

    from chipbench import harness
    bench, entry, cfg, tp = harness.cell_spec(cell)
    cfg = copy.deepcopy(cfg)
    cfg.update(TINY)
    cfg["service"]["penalty_rows"] = 2 * TINY["chunk_n"]
    cfg["reference"] = dict(cfg["reference"], power_iters=64)
    tp = dict(tp, pool_chunks=3, predict_rate_per_s=100,
              predict_rows_pool=256, refit_every_rows=2 * TINY["chunk_n"],
              max_refit_interval_rows=2 * TINY["chunk_n"])
    return bench, entry, cfg, tp


def run_tiny(cell: str, seed: int = 20251016, seconds: float = 1.0):
    """One run of `cell` at TINY with the Pallas kernels in interpret
    mode, past the harness's look for a chip; returns the result."""
    import time

    from chipbench import harness
    return harness.run_cell(cell, seed, seconds, False,
                            t_start=time.perf_counter(), require_tpu=False,
                            spec=tiny_spec(cell), peaks=PEAKS)
