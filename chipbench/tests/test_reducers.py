"""The reductions from trace, spans and counters to metrics: by hand on a
made-up trace, and on small traces recorded on a v5e chip (the first
seconds of a traced window of each cell, `data/`)."""
import glob
import json
import os

import pytest

from chipbench import harness, trace
from chipbench.tests.conftest import CONFIGS, HERE, PEAKS

# one device, a 10 s window; ops busy [1, 3] and [2, 4] (overlapping)
# and [6, 7]; the refit program [1, 4], a fold [6, 7]; the driver in
# bench.ingest over [4, 6] and a predict submit over [8, 9]
HAND = {
    "window": [0.0, 10.0],
    "devices": [{
        "name": "/device:TPU:0",
        "ops": [["fista", 1.0, 3.0], ["fista", 2.0, 4.0],
                ["rank_update", 6.0, 7.0]],
        "modules": [["jit_refit(12)", 1.0, 4.0],
                    ["jit__guarded_fold(3)", 6.0, 7.0]]}],
    "host": [["bench.window", 0.0, 10.0, "main"],
             ["bench.ingest", 4.0, 6.0, "main"],
             ["bench.predict", 8.0, 9.0, "gen"]],
}


def test_union_and_busy_by_hand():
    assert trace.union(HAND["devices"][0]["ops"]) == [[1.0, 4.0], [6.0, 7.0]]
    assert trace.busy_s(HAND) == 4.0
    assert trace.window_s(HAND) == 10.0


def test_module_time_by_hand_and_a_missing_program_is_an_error():
    assert trace.module_time(HAND, {"jit_refit"}) == 3.0
    assert trace.module_time(HAND, {"jit__guarded_fold"}) == 1.0
    with pytest.raises(LookupError):
        trace.module_time(HAND, {"jit_nothing"})


def test_clip_to_the_window():
    tr = dict(HAND, window=[2.0, 6.5])
    assert trace.busy_s(tr) == 2.0 + 0.5
    assert trace.module_time(tr, {"jit_refit"}) == 2.0


def test_idle_gaps_by_hand():
    gaps = trace.idle_gaps(HAND)
    # idle: [0, 1] none open, [4, 6] bench.ingest, [7, 10] bench.predict
    # open at its middle (8.5)
    assert gaps == [["bench.predict", 3.0], ["bench.ingest", 2.0],
                    ["no bench span", 1.0]]


def test_top_ops_by_hand():
    assert trace.top_ops(HAND) == [["jit_refit:fista", 4.0],
                                   ["jit__guarded_fold:rank_update", 1.0]]


def _ctx(cfg, window, obs, tr):
    return harness.Context(cfg, {}, window, 1.0, obs, tr, PEAKS)


def test_metrics_by_hand():
    cfg = dict(CONFIGS["tenants-m384-p1024"])
    obs = {"histograms": [
        {"name": "stream.refit.lasso_iters", "count": 1, "sum": 100},
        {"name": "stream.refit.debias_iters", "count": 1, "sum": 150},
        {"name": "serve.batch_rows", "count": 2, "sum": 10},
        {"name": "serve.batch.ms", "count": 1, "sum": 3.0},
        {"name": "serve.batch.ms", "count": 1, "sum": 5.0}]}
    ctx = _ctx(cfg, {"chunks": 1, "window_s": 10.0}, obs, HAND)
    assert harness.read_metric("front.rows_per_batch", ctx) == 5.0
    assert harness.read_metric("front.batch_ms", ctx) == 4.0
    assert harness.read_metric("refit.debias_iters", ctx) == 150.0
    assert harness.read_metric("device.idle_share", ctx) == 60.0
    from chipbench import work
    least = work.least_time(work.refit_phases(cfg["m"], cfg["p"], 1, 100,
                                                 150), PEAKS)
    assert harness.read_metric("refit_roofline", ctx) == \
        pytest.approx(100 * least / 3.0)
    assert harness.read_metric("service.refit_ms", ctx) is None


RECORDED = sorted(glob.glob(os.path.join(HERE, "data", "*.json")))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_trace(path):
    with open(path) as f:
        rec = json.load(f)
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell = {w["name"]: w for w in bench["workloads"]}[rec["cell"]]
    tr = rec["trace"]
    assert 0 < trace.busy_s(tr) <= trace.window_s(tr)
    ctx = _ctx(CONFIGS[cell["config"]], rec["window"], rec["obs"], tr)
    values = {m["name"]: harness.read_metric(m["name"], ctx)
              for m in harness.metric_names(bench, rec["cell"], True)}
    assert values == pytest.approx(rec["expected"])
    for name, v in values.items():
        assert v is not None and v > 0, name
        if name.endswith("roofline") or "mfu" in name or "share" in name:
            assert v <= 100.0, name
    ops, gaps = trace.top_ops(tr), trace.idle_gaps(tr)
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
