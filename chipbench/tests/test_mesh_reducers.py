"""The four-chip cell's per-layer reductions, by hand on made-up traces:
the straggler time and one chip's M solve against its roofline."""
import pytest

from chipbench import harness, work
from chipbench.tests.conftest import CONFIGS, PEAKS

CFG4 = CONFIGS["tenants4-m1024-p1024"]


def _read(name, cfg, trace, window=None, hists=()):
    obs = {"histograms": [{"name": n, "count": c, "sum": s, "max": mx}
                          for n, c, s, mx in hists]}
    ctx = harness.Context(cfg, {}, window or {"chunks": 0}, 1.0, obs, trace,
                          PEAKS)
    return harness.read_metric(name, ctx)


def _device(i, refits, msolve):
    """A chip that ran `refits` ([start, end] of each jit_refit) and
    spent `msolve` seconds of each in the M solve, phases attached."""
    return {"name": f"/device:TPU:{i}", "ops": [],
            "modules": [[f"jit_refit({i})", a, b] for a, b in refits],
            "phases": [["refit.msolve", a, a + msolve] for a, _ in refits]}


FOUR = {
    "window": [0.0, 20.0],
    # two refits; chip 2 is the slowest in both (3.4 s, then 3.2 s)
    "devices": [_device(0, [[1.0, 4.0], [10.0, 13.0]], 2.5),
                _device(1, [[1.0, 4.0], [10.0, 13.0]], 2.5),
                _device(2, [[1.0, 4.4], [10.0, 13.2]], 2.75),
                _device(3, [[1.0, 4.2], [10.0, 13.0]], 2.5)],
    "host": [["bench.window", 0.0, 20.0, "main"]],
}


def test_straggler_by_hand():
    # refit 1: max 3.4, mean 3.15; refit 2: max 3.2, mean 3.05
    got = _read("mesh.straggler_ms", CFG4, FOUR)
    assert got == pytest.approx(1e3 * (0.25 + 0.15) / 2)


def test_msolve_roofline_of_the_slowest_chip_by_hand():
    hists = [("stream.refit.shard_debias_iters", 8, 4 * 150 + 4 * 125, 150),
             ("stream.refit.debias_iters", 2, 300, 150)]
    got = _read("mesh.msolve_roofline", CFG4, FOUR, hists=hists)
    f, b = work.debias_step(CFG4["m"] // 4, CFG4["p"])
    least = work.least_time([(300 * f, 300 * b)], PEAKS)
    assert got == pytest.approx(100.0 * least / (2 * 2.75))
    assert 0 < got < 100


def test_mesh_metrics_read_nothing_without_the_mesh_spans():
    assert _read("mesh.msolve_roofline", CFG4, FOUR) is None
    assert _read("mesh.feed_ms", CFG4, None) is None
    assert _read("mesh.probe_ms", CFG4, None) is None
    assert _read("mesh.straggler_ms", CFG4, None) is None

