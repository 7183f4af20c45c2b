"""Every cell's driver at a tiny size on the CPU, Pallas kernels in
interpret mode: the run prints a contract-shaped last line with
`correct` true; the control (the reference in bfloat16 in the program's
place) fails the comparison; and a run whose timed path is broken
underneath comes out not correct, once for each fault the cells can
have."""
import json

import pytest

from chipbench import calibrate, check, harness
from chipbench.tests.conftest import run_tiny, tiny_spec

BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.fixture
def kernels_on(monkeypatch):
    from repro.kernels import common
    monkeypatch.setattr(common, "kernels_by_default", lambda: True)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_prints_a_contract_line(cell, kernels_on, capsys):
    harness.emit(run_tiny(cell))
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    want = {m["name"] for m in harness.metric_names(BENCH, cell, False)}
    assert set(line["metrics"]) == want
    for v in line["metrics"].values():
        assert v["value"] > 0 and v["unit"]
    assert err.strip().splitlines()[-1].startswith("bench: check ")


def _outputs(cell, seed=7):
    """A tiny run's outputs and data, with the program's own numbers."""
    from chipbench.traffic.stream import StreamRun
    _, _, cfg, tp = tiny_spec(cell)
    run = StreamRun(cfg, tp, seed, harness.log)
    run.setup()
    run.window(1.0)
    return cfg, run.outputs(check.SERVED_SAMPLE), run.pool


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_comparison(cell):
    cfg, out, pool = _outputs(cell)
    limits = check.load_limits(cell)
    assert check.verdict(check.compare(out, pool, cfg), limits)
    ctl = check.compare(out, pool, cfg, control=calibrate.CONTROL)
    assert not check.verdict(ctl, limits), ctl


def _fold_unchanged(orig):
    def fold(state, X, y, decay):
        _, health = orig(state, X, y, decay)
        return state, health
    return fold


def _half_batch(orig):
    def fold(state, X, y, decay):
        half = X.shape[1] // 2
        return orig(state, X[:, :half], y[:, :half], decay)
    return fold


def _refit_unchanged(orig):
    def refit(state, *args, **kwargs):
        new, info = orig(state, *args, **kwargs)
        return state._replace(generation=new.generation), info
    return refit


def _answer_altered(orig):
    def predict(beta_tilde, X):
        out = orig(beta_tilde, X)
        return out.at[0].set(out[1])
    return predict


FAULTS = {
    "fold_returns_state_unchanged": ("_guarded_fold", _fold_unchanged),
    "half_the_batch_left_out": ("_guarded_fold", _half_batch),
    "refit_returns_state_unchanged": ("refit", _refit_unchanged),
    "answer_altered_where_produced": ("_predict_shared", _answer_altered),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    from repro.stream import service
    name, wrap = FAULTS[fault]
    monkeypatch.setattr(service, name, wrap(getattr(service, name)))
    res = run_tiny(cell)
    assert res["correct"] is False, res["checks"]
