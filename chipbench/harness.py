"""One run of one cell, driven by data.

Everything that belongs to a configuration, a traffic mix or a metric
is found by name: the cell's entry in `BENCHMARK.json` names its
configuration (whose `file` holds the deployment) and its traffic mix
(`traffic/<name>.json`, which names its driver, `traffic/<driver>.py`);
each metric is read by `metrics/<name>.py`; the limits of the check are
in `limits/<cell>.json`; peaks are keyed by device kind in `peaks.json`.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell_spec(name: str) -> tuple:
    """(benchmark, cell entry, config, traffic) of a workload name."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(ROOT, cfg_entry["file"])
    traffic = load_json(HERE, "traffic", f"{cell['traffic']}.json")
    return bench, cell, cfg, traffic


def metric_names(bench: dict, cell: str, trace: bool) -> list:
    """The cell's end-to-end metrics, or with `trace` its per-layer ones."""
    def in_cell(m):
        return "workloads" not in m or cell in m["workloads"]
    e2e = [m for m in bench["end_to_end"] if in_cell(m)]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def read_metric(name: str, ctx):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name}", os.path.join(HERE, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


class Context:
    """What a metric reader sees."""

    def __init__(self, cfg, traffic, window, setup_s, obs_snapshot, trace,
                 peaks):
        self.cfg, self.traffic, self.window = cfg, traffic, window
        self.setup_s, self.obs, self.trace, self.peaks = (
            setup_s, obs_snapshot, trace, peaks)

    def hist(self, name: str):
        """count/sum/mean merged over every series of a histogram."""
        hs = [h for h in self.obs["histograms"] if h["name"] == name]
        count = sum(h["count"] for h in hs)
        if not count:
            return None
        total = sum(h["sum"] for h in hs)
        return {"count": count, "sum": total, "mean": total / count}


class CompileCounter:
    """Counts tracing, compilation and persistent-cache events."""

    def __init__(self):
        import jax
        self.counts = {}
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, _secs, **_kw):
        if event.endswith(("jaxpr_trace_duration", "backend_compile_duration")):
            self.counts[event.rsplit("/", 1)[-1]] = \
                self.counts.get(event.rsplit("/", 1)[-1], 0) + 1

    def _event(self, event, **_kw):
        if event.endswith(("cache_hits", "cache_misses")):
            self.counts[event.rsplit("/", 1)[-1]] = \
                self.counts.get(event.rsplit("/", 1)[-1], 0) + 1

    def snapshot(self) -> dict:
        return dict(self.counts)


def device_report(jax) -> tuple:
    devs = jax.local_devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return max(peaks), ", ".join(f"{d.id}:{b}" for d, b in zip(devs, peaks))


def autotune_report(cfg) -> None:
    from repro import obs
    from repro.kernels import autotune
    m, p, n = cfg["m"], cfg["p"], cfg["chunk_n"]
    events = {e: int(obs.counter_total("autotune.cache", event=e))
              for e in ("miss_sweep", "hit_disk", "hit_memory")}
    log(f"autotune events {events}, cache {autotune.cache_path()}")
    log("autotune winners: fista r=1 "
        f"{autotune.autotune_block(m, p, 1, sweep=False)}, fista r=p "
        f"{autotune.autotune_block(m, p, p, sweep=False)}, rank_update "
        f"{autotune.autotune_rank_block(m, n, p, sweep=False)}, logistic_grad "
        f"{autotune.autotune_logistic_block(m, n, p, sweep=False)}")


def summarize_window(w: dict) -> None:
    import numpy as np
    late = w["lateness_ms"]
    log(f"window {w['window_s']:.6f} s: {w['chunks']} chunks folded, "
        f"{w['rows_folded']} rows, {w['requests']} predict requests "
        f"({len(w['latency_ms'])} latency samples, "
        f"{w['requests_failed']} failed)")
    if len(late):
        log("generator lateness ms: p50 {:.4f} p99 {:.4f} max {:.4f}".format(
            *np.percentile(late, [50, 99, 100])))
    lat, due = w["latency_ms"], w["due_ms"]
    if len(lat):
        log("latency ms: p50 {:.4f} p90 {:.4f} p95 {:.4f} p99 {:.4f} "
            "max {:.4f}".format(*np.percentile(lat, [50, 90, 95, 99, 100])))
        edges = np.arange(0.0, w["window_s"] * 1e3 + 500.0, 500.0)
        which = np.digitize(due, edges)
        log("latency by due time (s: count max_ms): " + " ".join(
            f"{edges[b - 1] / 1e3:.1f}:{int(np.sum(which == b))} "
            f"{np.max(lat[which == b]):.0f}"
            for b in np.unique(which)))
    log("ingests (start_s end_s published): " + " ".join(
        f"{a:.3f}-{b:.3f}{'P' if p else ''}" for a, b, p in w["timeline"]))
    log(f"requests that waited through two refits: {waited_twice(w)}")


def waited_twice(w: dict) -> int:
    """Requests answered past the middle of the cycle after the publish
    they waited for: the front did not drain them between two refits."""
    import numpy as np
    ends = np.array([b for _, b, p in w["timeline"] if p]) * 1e3
    due = w["due_ms"]
    k = np.searchsorted(ends, due)
    nxt = k + 1 < len(ends)
    k = k[nxt]
    late = (due[nxt] + w["latency_ms"][nxt]
            > ends[k] + 0.5 * (ends[k + 1] - ends[k]))
    return int(np.sum(late))
    if w["arrivals_exhausted"] or w["generator_alive"]:
        log("WARNING: the predict generator ran out of arrivals or hung")


def device_peaks(kind: str) -> dict:
    """Peaks of a device kind from `peaks.json`; an unknown kind stops
    the run."""
    devices = load_json(HERE, "peaks.json")["devices"]
    if kind not in devices:
        raise SystemExit(f"bench: no peaks for device kind {kind!r}")
    return devices[kind]


def use_checkout_caches() -> str:
    """Put the program on the path, and JAX's compile cache and the
    autotune file at fixed paths inside the checkout, whatever the
    environment names; every compiled program is cached."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    cache = os.path.join(ROOT, ".cache", "jax")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.environ["REPRO_CACHE_DIR"] = os.path.dirname(cache)
    from repro.substrate import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True, spec=None,
             peaks=None, record=None) -> dict:
    """Set up, measure, check. Returns the result object (not printed).
    `peaks` defaults to those of the device's kind; `record` is a path
    for the traced run's reduced trace (see `main`)."""
    bench, cell, cfg, traffic = spec or cell_spec(cell_name)
    cache = use_checkout_caches()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or len(devices) < cell["chips"]):
        raise SystemExit(f"bench: needs {cell['chips']} TPU chip(s), found "
                         f"{len(devices)} {dev.platform!r} device(s)")
    peaks = peaks or device_peaks(dev.device_kind)
    log(f"cell {cell_name} seed {seed} seconds {seconds} trace {int(trace)} "
        f"on {len(devices)} x {dev.device_kind}, jax {jax.__version__}, "
        f"compile cache {cache}")

    from repro import obs
    from chipbench import check
    counter = CompileCounter()
    driver = importlib.import_module(f"chipbench.traffic.{traffic['driver']}")
    run = driver.StreamRun(cfg, traffic, seed, log)
    run.setup()
    autotune_report(cfg)
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    compiles0 = counter.snapshot()
    obs.reset()
    setup_s = time.perf_counter() - t_start
    window = run.window(seconds)
    compiles = {k: v - compiles0.get(k, 0)
                for k, v in counter.snapshot().items()
                if v != compiles0.get(k, 0)}
    tr = None
    if trace:
        jax.profiler.stop_trace()
    snapshot = obs.get_registry().snapshot()
    peak, per_device = device_report(jax)
    summarize_window(window)
    log(f"set-up {setup_s:.6f} s; compilation events in the window: "
        f"{compiles or 'none'}")
    log(f"peak bytes in use per device: {per_device}")

    out = run.outputs(check.SERVED_SAMPLE)
    pool = run.pool
    del run
    gc.collect()
    t_ref = time.perf_counter()
    numbers = check.compare(out, pool, cfg)
    log(f"reference and comparison: {time.perf_counter() - t_ref:.3f} s; "
        f"checked generation {out['generation']} after "
        f"{len(out['sequence'])} chunks, {len(out['served_rows'])} of "
        f"{out['responses']} responses")
    limits = check.load_limits(cell_name)
    correct = check.verdict(numbers, limits)

    if trace:
        from chipbench import trace as trace_mod
        tr = trace_mod.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = Context(cfg, traffic, window, setup_s, snapshot, tr, peaks)
    metrics = {}
    for m in metric_names(bench, cell_name, trace):
        v = read_metric(m["name"], ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    result = {
        "correct": bool(correct),
        "attempted": int(window["requests"] + window["chunks"]
                         + window["chunks_unfolded"]),
        "failed": int(window["requests_failed"] + window["chunks_unfolded"]),
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": int(peak)},
    }
    if tr is not None:
        from chipbench import trace as trace_mod
        result["device"]["busy_s"] = trace_mod.busy_s(tr)
        result["device"]["window_s"] = trace_mod.window_s(tr)
        result["breakdown"] = {"device_ops": trace_mod.top_ops(tr),
                               "idle_gaps": trace_mod.idle_gaps(tr)}
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                        for k in check.NAMES}
    if record and tr is not None:
        # what the per-layer readers read, for chipbench/tests/data
        keep = ("window_s", "chunks", "rows_folded")
        with open(record, "w") as f:
            json.dump({"cell": cell_name, "seed": seed, "trace": tr,
                       "window": {k: window[k] for k in keep},
                       "obs": {"histograms": [
                           {k: h[k] for k in ("name", "count", "sum")}
                           for h in snapshot["histograms"]]},
                       "expected": {k: v["value"]
                                    for k, v in metrics.items()}},
                      f, separators=(",", ":"))
    return result


def main(argv=None, *, t_start: float) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--record", default=None, metavar="PATH",
                    help="with --trace 1, also write the reduced trace, the "
                         "counters and the per-layer metrics to PATH")
    args = ap.parse_args(argv)
    emit(run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                  t_start=t_start, record=args.record))
    return 0


def emit(result: dict) -> None:
    """The compared numbers as the last lines of standard error, then the
    result as the last line of standard output."""
    for k, v in result["checks"].items():
        log(f"check {k} {v['value']!r} limit {v['limit']!r}")
    print(json.dumps(result), flush=True)
