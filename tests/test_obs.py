"""Telemetry subsystem tests: the `repro.obs` registry/exporters, and
the instrumentation contract of every subsystem that records into it.

Three layers:

* **Registry/exporter units** — counters, gauges, histograms, spans,
  thread safety, the REPRO_OBS=0 kill switch (in a subprocess, since
  it is read at import), Prometheus text, Chrome trace JSON, the
  snapshot round-trip, and the `python -m repro.obs` CLI.
* **Instrumentation ground truth** — the `dispatch.route` counters must
  agree with the `routes_to_oracle` / `rank_routes_to_oracle`
  predicates over an adversarial shape grid (kernel path, sliver,
  ragged, VMEM-budget bust); engine iteration counters must match
  `return_iters`; autotune cache events must follow the cold/warm/disk
  cycle with one timed candidate per sweep entry.
* **Measured collective bytes** — the obs byte ledger from a real
  8-device ingest probe must equal the arithmetic byte model, and the
  stream demo's Chrome trace must carry the ingest/refit/predict
  lifecycle.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.obs import export as obs_export
from repro.obs.registry import MAX_TRACE_EVENTS, Registry

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def fresh_registry():
    """Every test starts (and leaves) with an empty global registry —
    counters from other test modules must never leak into assertions
    here, and vice versa."""
    obs.reset()
    yield
    obs.reset()


# --- registry units -------------------------------------------------------

def test_counters_labels_and_superset_totals():
    obs.inc("t.calls", kernel="k1", outcome="a")
    obs.inc("t.calls", 2, kernel="k2", outcome="a")
    obs.inc("t.calls", kernel="k1", outcome="b")
    assert obs.counter_total("t.calls") == 4
    assert obs.counter_total("t.calls", kernel="k1") == 2
    assert obs.counter_total("t.calls", kernel="k1", outcome="a") == 1
    assert obs.counter_total("t.calls", kernel="nope") == 0


def test_gauges_and_histograms():
    obs.set_gauge("t.gauge", 1.0, shard="x")
    obs.set_gauge("t.gauge", 7.5, shard="x")     # last write wins
    for v in (1.0, 2.0, 6.0):
        obs.observe("t.lat", v, op="q")
    snap = obs.get_registry().snapshot()
    gauges = {(g["name"], tuple(sorted(g["labels"].items()))): g["value"]
              for g in snap["gauges"]}
    assert gauges[("t.gauge", (("shard", "x"),))] == 7.5
    st = obs.hist_stats("t.lat", op="q")
    assert st["count"] == 3 and st["sum"] == 9.0
    assert st["min"] == 1.0 and st["max"] == 6.0 and st["mean"] == 3.0
    assert obs.hist_stats("t.lat", op="missing") is None


def test_span_records_histogram_and_trace_event():
    with obs.span("t.step", phase="ingest"):
        pass
    st = obs.hist_stats("t.step.ms", phase="ingest")
    assert st is not None and st["count"] == 1 and st["max"] >= 0
    events = obs.get_registry().trace_events()
    assert len(events) == 1
    e = events[0]
    assert e["name"] == "t.step" and e["ph"] == "X" and e["cat"] == "repro"
    assert e["dur"] >= 0 and e["args"] == {"phase": "ingest"}


def test_disabled_registry_is_inert():
    reg = Registry(enabled=False)
    reg.inc("t.calls")
    reg.observe("t.lat", 1.0)
    reg.set_gauge("t.gauge", 1.0)
    with reg.span("t.step"):
        pass
    snap = reg.snapshot()
    assert snap["enabled"] is False
    assert snap["counters"] == [] and snap["histograms"] == []
    assert snap["gauges"] == [] and reg.trace_events() == []


def test_span_lands_in_the_profiler_trace(tmp_path):
    """With the jax.profiler hook on, a span is also an event named after
    it on the profiler's host plane (its labels stay out of the name); a
    disabled registry puts nothing there; with no trace active the hook
    opens nothing."""
    from jax.profiler import ProfileData

    from repro.obs import jaxprof, registry
    jaxprof.annotate_spans()
    assert registry._annotation_hook("x.idle") is None
    off = Registry(enabled=False)
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("x.y", label="kept"):
            pass
        with off.span("x.off"):
            pass
    path, = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    host = ProfileData.from_file(str(path)).find_plane_with_name("/host:CPU")
    names = [ev.name for line in host.lines for ev in line.events]
    assert names.count("x.y") == 1
    assert "x.off" not in names
    assert obs.hist_stats("x.y.ms", label="kept")["count"] == 1


def test_trace_event_cap_drops_and_counts():
    reg = Registry()
    for _ in range(MAX_TRACE_EVENTS + 5):
        with reg.span("t.e"):
            pass
    assert len(reg.trace_events()) == MAX_TRACE_EVENTS
    assert reg.snapshot()["dropped_trace_events"] == 5


def test_thread_safety_of_counters():
    n_threads, n_incs = 8, 2500

    def worker():
        for _ in range(n_incs):
            obs.inc("t.parallel", worker="w")

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert obs.counter_total("t.parallel") == n_threads * n_incs


def test_repro_obs_env_kill_switch():
    """REPRO_OBS=0 hard-disables at import; checked in a subprocess
    because the flag is read when `repro.obs` first loads."""
    code = (
        "from repro import obs\n"
        "obs.inc('x.calls')\n"
        "with obs.span('x.step'):\n"
        "    pass\n"
        "assert not obs.enabled()\n"
        "snap = obs.get_registry().snapshot()\n"
        "assert snap['enabled'] is False\n"
        "assert snap['counters'] == [] and snap['histograms'] == []\n"
        "print('DISABLED_OK')\n"
    )
    env = dict(os.environ, REPRO_OBS="0")
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "DISABLED_OK" in r.stdout


# --- exporters ------------------------------------------------------------

def test_prometheus_text_format():
    obs.inc("t.calls", 3, kernel="k")
    obs.observe("t.lat", 2.0)
    text = obs_export.to_prometheus(obs_export.snapshot())
    assert 'repro_t_calls_total{kernel="k"} 3' in text
    assert "repro_t_lat_count 1" in text
    assert "repro_t_lat_sum 2.0" in text


def test_snapshot_write_load_roundtrip(tmp_path):
    obs.inc("t.calls", kernel="k")
    path = tmp_path / "deep" / "snap.json"     # exporter makedirs
    written = obs_export.write_snapshot(str(path), meta={"backend": "cpu"})
    loaded = obs_export.load_snapshot(str(path))
    assert loaded == json.loads(json.dumps(written))
    assert loaded["meta"]["backend"] == "cpu"
    assert loaded["counters"][0]["name"] == "t.calls"


def test_chrome_trace_roundtrip(tmp_path):
    with obs.span("t.step", op="x"):
        pass
    path = tmp_path / "trace.json"
    obs_export.write_chrome_trace(str(path))
    trace = json.loads(path.read_text())
    assert trace["displayTimeUnit"] == "ms"
    (e,) = trace["traceEvents"]
    assert e["name"] == "t.step" and e["ph"] == "X"
    assert set(e) >= {"ts", "dur", "pid", "tid", "args"}


def test_cli_summary_and_prometheus(tmp_path):
    obs.inc("cli.calls", 5, kernel="k")
    path = tmp_path / "snap.json"
    obs_export.write_snapshot(str(path), meta={"backend": "cpu"})
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-m", "repro.obs", str(path)],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "cli.calls" in r.stdout and "backend: cpu" in r.stdout
    r2 = subprocess.run(
        [sys.executable, "-m", "repro.obs", "--prometheus", str(path)],
        env=env, capture_output=True, text=True, timeout=120)
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert 'repro_cli_calls_total{kernel="k"} 5' in r2.stdout


# --- dispatcher routing counters vs predicate ground truth ----------------

# (m, n, p, expected outcome, expected reason) — the adversarial grid:
# aligned kernel shapes (including the feature-tiled p = 8192 slab),
# the n = 1016 = 8*127 sliver trap, a ragged batch, and the p = 16384
# accumulator-busts-VMEM regime.
LOGISTIC_ROUTE_CASES = (
    (2, 128, 256, "kernel", "kernel"),
    (1, 8, 8192, "kernel", "kernel"),
    (2, 1016, 128, "oracle", "sliver"),
    (2, 100, 64, "oracle", "ragged"),
    (1, 8, 16384, "oracle", "vmem_budget"),
)


@pytest.mark.parametrize("m,n,p,outcome,reason", LOGISTIC_ROUTE_CASES)
def test_logistic_route_counters_match_predicate(m, n, p, outcome, reason):
    from repro.kernels.logistic_grad.ops import (
        logistic_grad, routes_to_oracle,
    )
    assert routes_to_oracle(n, p) == (outcome == "oracle")
    Xs = jnp.ones((m, n, p), jnp.float32)
    ys = jnp.ones((m, n), jnp.float32)
    B = jnp.zeros((m, p), jnp.float32)
    out = logistic_grad(Xs, ys, B, interpret=True)
    assert out.shape == (m, p)
    assert obs.counter_total("dispatch.route", kernel="logistic_grad",
                             outcome=outcome) == 1
    assert obs.counter_total("dispatch.route", kernel="logistic_grad",
                             outcome=outcome, reason=reason) == 1
    other = "oracle" if outcome == "kernel" else "kernel"
    assert obs.counter_total("dispatch.route", kernel="logistic_grad",
                             outcome=other) == 0


RANK_ROUTE_CASES = (
    (2, 128, 64, 128, "kernel", "kernel"),
    (2, 1016, 64, 128, "oracle", "sliver"),
    (2, 100, 64, 128, "oracle", "ragged"),
    (1, 256, 2048, (2048, 256), "oracle", "vmem_budget"),
)


@pytest.mark.parametrize("m,n,p,block,outcome,reason", RANK_ROUTE_CASES)
def test_rank_route_counters_match_predicate(m, n, p, block, outcome,
                                             reason):
    from repro.kernels.rank_update.ops import (
        rank_routes_to_oracle, rank_update,
    )
    assert rank_routes_to_oracle(n, p, block) == (outcome == "oracle")
    Xs = jnp.ones((m, n, p), jnp.float32)
    ys = jnp.ones((m, n), jnp.float32)
    Sig, c = rank_update(Xs, ys, block=block, use_kernel=True,
                         interpret=True)
    assert Sig.shape == (m, p, p) and c.shape == (m, p)
    assert obs.counter_total("dispatch.route", kernel="rank_update",
                             outcome=outcome, reason=reason) == 1


def test_rank_backend_routing_labeled_distinctly():
    """use_kernel=False on a kernel-eligible shape is an oracle route
    for a BACKEND reason, not a shape reason — the counters must keep
    that distinction or the route mix on CPU reads as a kernel bug."""
    from repro.kernels.rank_update.ops import rank_update
    Xs = jnp.ones((2, 128, 64), jnp.float32)
    ys = jnp.ones((2, 128), jnp.float32)
    rank_update(Xs, ys, use_kernel=False)
    assert obs.counter_total("dispatch.route", kernel="rank_update",
                             outcome="oracle", reason="backend") == 1


# --- engine iteration accounting ------------------------------------------

def _toy_lasso(m=2, p=8, n=64):
    key = jax.random.PRNGKey(0)
    A = jax.random.normal(key, (m, n, p), jnp.float32)
    Sigmas = jnp.einsum("tnp,tnq->tpq", A, A) / n \
        + 0.5 * jnp.eye(p, dtype=jnp.float32)
    cs = jnp.mean(A, axis=1)
    return Sigmas, cs


def test_engine_iteration_counters_match_return_iters():
    from repro.core.engine import solve_lasso_batched
    Sigmas, cs = _toy_lasso()
    out, n_iters = solve_lasso_batched(Sigmas, cs, 0.1, iters=400,
                                       tol=1e-6, return_iters=True)
    used = int(n_iters)
    assert 0 < used < 400                      # tol fired before ceiling
    assert obs.counter_total("engine.solve.calls", kind="lasso") == 1
    assert obs.counter_total("engine.solve.early_exit", kind="lasso") == 1
    st = obs.hist_stats("engine.solve.iters_used", kind="lasso")
    assert st["count"] == 1 and st["max"] == used
    st_ceiling = obs.hist_stats("engine.solve.iters_ceiling", kind="lasso")
    assert st_ceiling["max"] == 400


def test_engine_records_nothing_under_external_jit():
    """A caller that jits the public wrapper must not crash on the
    recording path, and must record nothing (the counters would
    otherwise tally compilations, not solves)."""
    from repro.core.engine import solve_lasso_batched
    Sigmas, cs = _toy_lasso()

    @jax.jit
    def run(S, c):
        return solve_lasso_batched(S, c, 0.1, iters=50)

    jax.block_until_ready(run(Sigmas, cs))
    assert obs.counter_total("engine.solve.calls") == 0
    assert obs.hist_stats("engine.solve.iters_used") is None


# --- autotune cache events ------------------------------------------------

def test_autotune_cache_event_cycle(tmp_path, monkeypatch):
    from repro.kernels import autotune
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    autotune.clear_memory_cache()
    timed = []

    def fake_time(fn, reps):
        timed.append(fn)
        return float(len(timed))               # first candidate wins

    monkeypatch.setattr(autotune, "_time_candidate", fake_time)
    n_cands = len(autotune.block_candidates(64, 1))

    cold = autotune.autotune_block(2, 64, 1, backend="cpu",
                                   interpret=True, reps=1)
    assert obs.counter_total("autotune.cache", kernel="fista_step",
                             event="miss_sweep") == 1
    assert len(timed) == n_cands               # every candidate timed once
    st = obs.hist_stats("autotune.candidate_us", kernel="fista_step")
    assert st["count"] == n_cands
    assert obs.hist_stats("autotune.sweep.ms", kernel="fista_step") \
        is not None

    warm = autotune.autotune_block(2, 64, 1, backend="cpu",
                                   interpret=True, reps=1)
    assert obs.counter_total("autotune.cache", kernel="fista_step",
                             event="hit_memory") == 1
    autotune.clear_memory_cache()
    disk = autotune.autotune_block(2, 64, 1, backend="cpu",
                                   interpret=True, reps=1)
    assert obs.counter_total("autotune.cache", kernel="fista_step",
                             event="hit_disk") == 1
    assert len(timed) == n_cands               # hits never re-time
    assert cold == warm == disk
    autotune.clear_memory_cache()


# --- measured collective bytes (8-device probe) ---------------------------

def test_measured_psum_bytes_match_model():
    """The obs byte ledger from one real sharded ingest must equal the
    arithmetic model: 2 traced psum_stats (Sigma and c), each counted
    at local nbytes × data-axis size. For the default (m=8, n=64,
    p=200) probe on a data=4 x task=2 mesh that is
    4 * (4*200*200*4 + 4*200*4) = 2,572,800 bytes."""
    sys.path.insert(0, os.path.join(str(REPO), "benchmarks"))
    from communication import measured_collective_bytes
    rec = measured_collective_bytes()
    assert rec["probe_ok"], rec
    assert rec["psum_calls"] == 2
    assert rec["expected_bytes"] == 2_572_800
    assert rec["psum_bytes"] == rec["expected_bytes"]
    assert rec["matches_model"]


# --- stream service timeline ----------------------------------------------

def test_stream_online_chrome_trace_lifecycle(tmp_path):
    """`stream_online --smoke --obs-out` must produce a valid Chrome
    trace-event JSON whose timeline carries the full service lifecycle
    (ingest, refit, predict spans), plus telemetry-derived headline
    metrics consistent with the run."""
    from examples.stream_online import main as stream_main
    out = tmp_path / "obs.json"
    met = stream_main(["--smoke", "--obs-out", str(out)])
    trace = json.loads((tmp_path / "obs.trace.json").read_text())
    assert trace["displayTimeUnit"] == "ms"
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"stream.ingest", "stream.refit", "stream.predict"} <= names
    for e in trace["traceEvents"]:
        assert e["ph"] == "X" and e["dur"] >= 0 and e["ts"] >= 0
    snap = json.loads(out.read_text())
    assert snap["meta"]["example"] == "stream_online"
    # smoke run: 8 chunks ingested, 4 stream refits + 1 final
    assert obs.counter_total("stream.ingest.chunks") == 8
    assert met["obs_refits_recorded"] == met["refits_during_stream"] + 1
    assert met["obs_ingest_rows_per_s"] > 0
    assert met["obs_refit_latency_ms"] > 0
