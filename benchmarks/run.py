"""Benchmark driver: one section per paper table/figure + system benches.

Prints ``name,us_per_call,derived`` CSV rows (the repo contract). Heavy
experiment sweeps persist JSON artifacts under experiments/paper/.

  fig1   — paper Figure 1 (regression, vary n / vary m)
  fig2   — paper Figure 2 (classification, vary n / vary m)
  comm   — paper Table 1 communication column (+ one-round HLO proof)
  rates  — Tables 1-2 rate sanity (error scaling vs n and m)
  kern   — kernel microbenches
  serve  — streaming serving front (p99 under load, ingest-while-serving)
  roof   — dry-run / roofline summary (reads experiments/dryrun)

Usage: python -m benchmarks.run [--only fig1,comm] [--runs N]
                                [--json-out BENCH_kernels.json]
                                [--telemetry PATH]

`--json-out` additionally persists the machine-readable sections (kern
and serve) as JSON: `{"meta": {...}, "rows": [...]}` — run metadata
(backend, device count, jax version, git SHA) plus the final telemetry
snapshot under `meta`, one object per benchmark row (name/us plus any
derived fields like flops and speedup) under `rows` — so the perf
trajectory is tracked across PRs AND attributable to the environment
that produced it. Select ONE machine-readable section per artifact
(`--only kern --json-out BENCH_kernels.json`, `--only serve --json-out
BENCH_serve.json`); `benchmarks/check_regression.py` gates on both
files (it also still reads the pre-PR-7 flat-list format).
`--telemetry PATH` writes the full obs snapshot of the whole benchmark
run as its own artifact.
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback


def run_metadata() -> dict:
    """Environment stamp for benchmark artifacts. Imports jax lazily —
    this module must stay importable (for `rows_to_json`) without
    paying a backend init."""
    import platform
    import subprocess

    import jax

    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short=12", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=__file__.rsplit("/", 2)[0] or ".",
        ).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        sha = "unknown"
    return {
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "jax_version": jax.__version__,
        "python_version": platform.python_version(),
        "platform": platform.platform(),
        "git_sha": sha,
    }


def rows_to_json(rows) -> list:
    """Parse ``name,us,k=v,...`` benchmark rows into JSON objects.

    Numeric derived fields are parsed as floats (a trailing ``x`` on
    speedups is stripped); anything unparsable stays a string.
    """
    out = []
    for row in rows:
        parts = row.split(",")
        d = {"name": parts[0], "us": float(parts[1])}
        for extra in parts[2:]:
            k, _, v = extra.partition("=")
            try:
                d[k] = float(v[:-1] if v.endswith("x") else v)
            except ValueError:
                d[k] = v
        out.append(d)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig1,fig2,comm,rates,kern,serve,roof")
    ap.add_argument("--runs", type=int, default=5,
                    help="averaging runs for the paper sweeps")
    ap.add_argument("--json-out", default=None, metavar="PATH",
                    help="also write the machine-readable rows (kern / "
                         "serve sections) as JSON to PATH")
    ap.add_argument("--telemetry", default=None, metavar="PATH",
                    help="write the run's repro.obs snapshot to PATH")
    args = ap.parse_args()
    want = set(args.only.split(",")) if args.only else None

    sections = []
    if want is None or "comm" in want:
        from benchmarks.communication import main as comm_main
        sections.append(("comm", comm_main))
    if want is None or "kern" in want:
        from benchmarks.kernels_bench import main as kern_main
        sections.append(("kern", kern_main))
    if want is None or "serve" in want:
        from benchmarks.stream_bench import serve_rows as serve_main
        sections.append(("serve", lambda: serve_main(smoke=True)))
    if want is None or "rates" in want:
        from benchmarks.rates import main as rates_main
        sections.append(("rates",
                         lambda: rates_main(n_runs=max(3, args.runs // 2))))
    if want is None or "fig1" in want:
        from benchmarks.fig1_regression import main as fig1_main
        sections.append(("fig1", lambda: fig1_main(n_runs=args.runs)))
    if want is None or "fig2" in want:
        from benchmarks.fig2_classification import main as fig2_main
        sections.append(("fig2", lambda: fig2_main(n_runs=args.runs)))
    if want is None or "roof" in want:
        from benchmarks.roofline import main as roof_main
        sections.append(("roof", roof_main))

    print("name,us_per_call,derived")
    failures = 0
    json_rows = []   # rows from machine-readable sections, in run order
    JSONABLE = {"kern", "serve"}
    for name, fn in sections:
        try:
            rows = fn()
            for row in rows:
                print(row, flush=True)
            if name in JSONABLE and args.json_out:
                json_rows.extend(rows)
        except Exception:
            failures += 1
            print(f"{name}_FAILED,0,see stderr", flush=True)
            traceback.print_exc()
    if args.json_out and json_rows:
        from repro import obs
        artifact = {
            "meta": {**run_metadata(), "telemetry": obs.snapshot()},
            "rows": rows_to_json(json_rows),
        }
        with open(args.json_out, "w") as f:
            json.dump(artifact, f, indent=2)
            f.write("\n")
        print(f"# wrote {args.json_out}", file=sys.stderr)
    if args.telemetry:
        from repro.obs import export as obs_export
        obs_export.write_snapshot(args.telemetry, meta=run_metadata())
        print(f"# wrote {args.telemetry}", file=sys.stderr)
    if args.json_out and not json_rows:
        # never exit 0 leaving a stale baseline: no machine-readable
        # section ran to completion, so the requested JSON was not
        # produced
        print(f"ERROR: --json-out {args.json_out} requested but no "
              "machine-readable section (kern/serve) ran to completion",
              file=sys.stderr)
        failures += 1
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    from repro.substrate import enable_compile_cache
    enable_compile_cache()
    main()
