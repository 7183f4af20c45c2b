"""The four-chip cell's driver (`traffic/stream_mesh.py`) at the tiny
size on four CPU devices, in a subprocess (the device count is fixed
when JAX starts): the service runs task-sharded over the configuration's
data=1 x task=4 mesh, the run prints `correct` true (once with the
Pallas kernels in interpret mode under `shard_map`), the control fails
the comparison, and a run whose timed path is broken underneath comes
out not correct, once for each fault the cell can have."""
import json
import re

import pytest

from chipbench.tests.conftest import ROOT
from repro.substrate import run_probe

CELL = "tenants4.refit"

_PROBE = r"""
import json, sys, time
from chipbench import calibrate, check, harness
from chipbench.tests.conftest import PEAKS, tiny_spec
from chipbench.traffic.stream_mesh import StreamRun
from repro import obs
from repro.kernels import common
from repro.stream import service

CELL, SEED = %(cell)r, 20251016


def run():
    res = harness.run_cell(CELL, SEED, 1.0, False,
                           t_start=time.perf_counter(), require_tpu=False,
                           spec=tiny_spec(CELL), peaks=PEAKS)
    hists = {h["name"]: h["count"]
             for h in obs.get_registry().snapshot()["histograms"]}
    res["feeds"] = hists.get("stream.ingest.feed.ms", 0)
    return res


def report(case, **values):
    print("CASE " + json.dumps(dict(values, case=case)), flush=True)


kernels_by_default = common.kernels_by_default
common.kernels_by_default = lambda: True
res = run()
common.kernels_by_default = kernels_by_default
report("kernels", correct=res["correct"], checks=res["checks"],
       failed=res["failed"], attempted=res["attempted"], feeds=res["feeds"],
       metrics=sorted(res["metrics"]))

_, _, cfg, tp = tiny_spec(CELL)
r = StreamRun(cfg, tp, 7, harness.log)
r.setup()
r.window(1.0)
on_mesh = r.svc.mesh is not None
out, pool = r.outputs(check.SERVED_SAMPLE), r.pool
limits = check.load_limits(CELL)
report("control", on_mesh=on_mesh,
       program=check.verdict(check.compare(out, pool, cfg), limits),
       control=check.verdict(check.compare(out, pool, cfg,
                                           control=calibrate.CONTROL), limits))


def fold_unchanged(orig):
    def fold(state, X, y, *a, **k):
        orig(state, X, y, *a, **k)
        return state
    return fold


def half_batch(orig):
    def fold(state, X, y, *a, **k):
        half = X.shape[1] // 2
        return orig(state, X[:, :half], y[:, :half], *a, **k)
    return fold


def tasks_moved_between_shards(orig):
    def feed(X, y, *a, **k):
        shift = X.shape[0] // 4
        return orig(X[list(range(shift, X.shape[0])) + list(range(shift))],
                    y, *a, **k)
    return feed


def refit_unchanged(orig):
    def refit(state, *a, **k):
        new, info = orig(state, *a, **k)
        return state._replace(generation=new.generation), info
    return refit


def answer_altered(orig):
    def predict(beta_tilde, X):
        out = orig(beta_tilde, X)
        return out.at[0].set(out[1])
    return predict


FAULTS = {
    "fold_returns_state_unchanged": ("ingest_sharded", fold_unchanged),
    "half_the_batch_left_out": ("ingest_sharded", half_batch),
    "tasks_moved_between_shards": ("feed_chunk", tasks_moved_between_shards),
    "refit_returns_state_unchanged": ("refit", refit_unchanged),
    "answer_altered_where_produced": ("_predict_shared", answer_altered),
}
for fault, (name, wrap) in sorted(FAULTS.items()):
    orig = getattr(service, name)
    setattr(service, name, wrap(orig))
    try:
        res = run()
    finally:
        setattr(service, name, orig)
    report(fault, correct=res["correct"], checks=res["checks"],
           feeds=res["feeds"])
"""

FAULTS = ("answer_altered_where_produced", "fold_returns_state_unchanged",
          "half_the_batch_left_out", "refit_returns_state_unchanged",
          "tasks_moved_between_shards")


@pytest.fixture(scope="module")
def cases():
    res = run_probe(_PROBE % {"cell": CELL}, n_devices=4, timeout=1500,
                    cwd=ROOT)
    assert res.returncode == 0, res.stderr[-3000:]
    found = {}
    for line in re.findall(r"^CASE (.*)$", res.stdout, re.M):
        case = json.loads(line)
        found[case.pop("case")] = case
    return found


def test_mesh_cell_is_correct_with_kernels_on_four_devices(cases):
    c = cases["kernels"]
    assert c["correct"] is True, c["checks"]
    assert c["failed"] == 0 and c["attempted"] > 0
    # the window's chunks went through the mesh's feed
    assert c["feeds"] > 0
    assert c["metrics"] == ["ingest_rows_per_s", "predict_p95_ms", "setup_s"]


def test_control_fails_the_mesh_cell(cases):
    c = cases["control"]
    assert c["on_mesh"] and c["program"] and not c["control"]


@pytest.mark.parametrize("fault", FAULTS)
def test_broken_mesh_path_is_not_correct(cases, fault):
    c = cases[fault]
    assert c["feeds"] > 0
    assert c["correct"] is False, c["checks"]
