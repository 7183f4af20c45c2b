#!/usr/bin/env python3
"""Readings that the check's limits and the predict rate are set from.
Not part of a benchmark run; run it on the chip by hand.

    python3 chipbench/calibrate.py readings --workload tenants.refit \
        --seconds 10 --seeds 11,12,13 --control-seeds 11,12,13
    python3 chipbench/calibrate.py sweep --workload tenants.refit \
        --seconds 20 --seeds 5 --rates 500,1000,2000,4000

`readings` runs, in one process, the cell's set-up, window and check for
each seed and prints the compared numbers of the program (the lower
readings). For the control seeds it also prints those of the control,
the plain reference with every operand rounded to float8_e4m3fn put in
the program's place (the upper readings), and of a witness, the same
with operands rounded to bfloat16: the precision the configuration
states, which the program should read like. `sweep` sets the cell up
once and runs one window per rate, printing the latency quantiles, how
late the generator ran and whether the backlog grew.
"""
import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CONTROL = "float8_e4m3fn"
WITNESS = "bfloat16"


def _setup(cell, seed):
    from chipbench import harness
    _, _, cfg, tp = harness.cell_spec(cell)
    harness.use_checkout_caches()
    import importlib
    driver = importlib.import_module(f"chipbench.traffic.{tp['driver']}")
    run = driver.StreamRun(cfg, tp, seed, harness.log)
    run.setup()
    return cfg, run


def readings(args):
    import numpy as np

    from chipbench import check
    control = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        cfg, run = _setup(args.workload, seed)
        t1 = time.perf_counter()
        w = run.window(args.seconds)
        out, pool = run.outputs(check.SERVED_SAMPLE), run.pool
        del run
        gc.collect()
        t2 = time.perf_counter()
        row = {"seed": seed, "program": check.compare(out, pool, cfg),
               "reference_s": time.perf_counter() - t2,
               "generation": out["generation"],
               "chunks": len(out["sequence"]),
               "responses": out["responses"], "window_s": w["window_s"],
               "rows_per_s": w["rows_folded"] / w["window_s"],
               "p95_ms": float(np.percentile(w["latency_ms"], 95)),
               "requests": w["requests"], "failed": w["requests_failed"],
               "setup_s": t1 - t0}
        if seed in control:
            for key, dtype in (("control", CONTROL), ("witness", WITNESS)):
                row[key] = check.compare(out, pool, cfg, control=dtype)
        print("READING " + json.dumps(row), flush=True)
        del out, pool
        gc.collect()


def backlog_minima(due_ms, latency_ms, window_s, cycles):
    """The fewest requests outstanding (due, not yet answered) within
    each of `cycles` equal parts of the window: where the front drains
    between refits these stay near 0; where it cannot keep up they
    grow from cycle to cycle."""
    import numpy as np
    due = np.sort(due_ms)
    done = np.sort(due_ms + latency_ms)
    grid = np.arange(0.0, window_s * 1e3, 5.0)
    backlog = (np.searchsorted(due, grid, side="right")
               - np.searchsorted(done, grid, side="right"))
    parts = np.array_split(backlog, max(cycles, 1))
    return [int(p.min()) for p in parts if len(p)]


def sweep(args):
    """One window per rate after one set-up. A rate is sustained where
    the backlog's minimum in the window's last cycle is at most a
    twentieth of a second of arrivals (or one full microbatch), and no
    request waited through two refits (`harness.waited_twice`)."""
    import numpy as np

    from chipbench import harness
    _, run = _setup(args.workload, int(args.seeds.split(",")[0]))
    for rate in (float(r) for r in args.rates.split(",")):
        run.tp = dict(run.tp, predict_rate_per_s=rate)
        run.front.start()
        w = run.window(args.seconds)
        harness.summarize_window(w)
        lat = w["latency_ms"]
        minima = backlog_minima(w["due_ms"], lat, w["window_s"],
                                w["publishes"])
        twice = harness.waited_twice(w)
        row = {"rate": rate, "requests": w["requests"],
               "failed": w["requests_failed"],
               "window_s": w["window_s"], "publishes": w["publishes"],
               "p50": float(np.percentile(lat, 50)),
               "p95": float(np.percentile(lat, 95)),
               "p99": float(np.percentile(lat, 99)),
               "lateness_p99": float(np.percentile(w["lateness_ms"], 99)),
               "backlog_minima": minima,
               "max_ms": float(np.max(lat)), "waited_twice": twice,
               "sustained": bool(w["requests_failed"] == 0 and minima
                                 and minima[-1] <= max(64, rate / 20)
                                 and twice == 0),
               "rows_per_s": w["rows_folded"] / w["window_s"]}
        print("SWEEP " + json.dumps(row), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("readings", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    (readings if args.mode == "readings" else sweep)(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
