"""The refit's phases and the program's own spans in a trace: by hand on
a made-up trace, and the premise the phase map rests on (the op names of
a profile are the instruction names of the program compiled again from
the cell's shapes) on a CPU profile of a tiny refit."""
import glob
import time

import pytest

from chipbench import harness, phases, trace
from chipbench.tests.conftest import CONFIGS, PEAKS, tiny_spec

# one device, a 10 s window, one refit [1, 6]: power [1, 1.5], lasso
# [1.5, 2.5], then the M solve in two steps [2.5, 3.5] and [4, 5] with an
# unscoped copy [3.5, 4] between them, an unscoped op [5, 5.2] between
# the M solve and the debias [5.2, 5.5], and the threshold [5.5, 6]; a
# fold [7, 8]. The driver in bench.ingest and the program's stream.ingest
# over [6, 7], in stream.ingest.fold over [6, 6.6] and in
# stream.ingest.guard over [6.6, 7]; a predict waits in serve.batch
# over [8, 10].
OP_PHASE = {"power": "refit.power", "lasso": "refit.lasso",
            "fista": "refit.msolve", "copy.4": None, "add.1": None,
            "debias": "refit.debias", "threshold": "refit.threshold"}
OPS = [["%power", 1.0, 1.5], ["%lasso", 1.5, 2.5], ["%fista", 2.5, 3.5],
       ["%copy.4", 3.5, 4.0], ["%fista", 4.0, 5.0], ["%add.1", 5.0, 5.2],
       ["%debias", 5.2, 5.5], ["%threshold", 5.5, 6.0]]
HAND = {
    "window": [0.0, 10.0],
    "devices": [{
        "name": "/device:TPU:0",
        "ops": OPS + [["%rank_update", 7.0, 8.0]],
        "modules": [["jit_refit(12)", 1.0, 6.0],
                    ["jit__guarded_fold(3)", 7.0, 8.0]]}],
    "host": [["bench.window", 0.0, 10.0, "main"],
             ["bench.ingest", 6.0, 7.0, "main"],
             ["stream.ingest", 6.0, 7.0, "main"],
             ["stream.ingest.fold", 6.0, 6.6, "main"],
             ["stream.ingest.guard", 6.6, 7.0, "main"],
             ["serve.batch", 8.0, 10.0, "front"]],
}


def _ctx(tr, cfg=None, hists=()):
    obs = {"histograms": [{"name": n, "count": c, "sum": s}
                          for n, c, s in hists]}
    return harness.Context(cfg or CONFIGS["tenants-m384-p1024"], {},
                           {"chunks": 1, "window_s": 10.0}, 1.0, obs, tr,
                           PEAKS)


def test_hlo_op_phases_by_hand():
    hlo = "\n".join([
        '  %fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, calls=%f, '
        'metadata={op_name="jit(refit)/refit.lasso/while/body/mul" '
        'stack_frame_id=4}',
        '  ROOT %copy.7 = f32[4]{0} copy(%fusion.3)',
        '  %custom-call.2 = f32[4]{0} custom-call(%a), '
        'metadata={op_name="jit(refit)/shard_map/refit.msolve/pallas_call"}',
        '  %add.1 = s32[] add(%a, %b), '
        'metadata={op_name="jit(refit)/add"}'])
    assert phases.hlo_op_phases(hlo) == {
        "fusion.3": "refit.lasso", "copy.7": None,
        "custom-call.2": "refit.msolve", "add.1": None}


def test_assign_unscoped_ops_between_ops_of_one_phase():
    got = phases.assign(OPS, OP_PHASE)
    # the copy lies between two M-solve steps; the add between the M
    # solve and the debias, so it stays unclaimed
    assert [e[0] for e in got] == [
        "refit.power", "refit.lasso", "refit.msolve", "refit.msolve",
        "refit.msolve", "refit.debias", "refit.threshold"]
    assert got[3] == ["refit.msolve", 3.5, 4.0]


def test_executions_hold_only_the_refits_ops():
    runs = phases.executions(HAND["devices"][0])
    assert len(runs) == 1 and [o[0] for o in runs[0]] == [o[0] for o in OPS]


def test_phase_metrics_by_hand():
    tr = {**HAND, "devices": [dict(HAND["devices"][0],
                                   phases=phases.assign(OPS, OP_PHASE))]}
    cfg = CONFIGS["tenants-m384-p1024"]
    ctx = _ctx(tr, cfg, [("stream.refit.lasso_iters", 1, 100),
                         ("stream.refit.debias_iters", 1, 150),
                         ("serve.queue_ms", 4, 10.0),
                         ("stream.ingest.ms", 2, 6.0)])
    assert phases.busy_s(ctx, "refit.msolve") == 2.5
    assert harness.read_metric("refit.msolve_ms", ctx) == 2500.0
    assert harness.read_metric("refit.lasso_ms", ctx) == 1000.0
    from chipbench import work
    f, b = work.debias_step(cfg["m"], cfg["p"])
    least = work.least_time([(150 * f, 150 * b)], PEAKS)
    assert harness.read_metric("msolve_roofline", ctx) == \
        pytest.approx(100 * least / 2.5)
    assert harness.read_metric("front.queue_ms", ctx) == 2.5
    assert harness.read_metric("service.ingest_ms", ctx) == 3.0
    # clipped to the window: only [2.5, 3.5] and [3.5, 4] of the M solve
    tr_cut = dict(tr, window=[0.0, 4.0])
    assert phases.busy_s(_ctx(tr_cut, cfg), "refit.msolve") == 1.5


def test_phase_metrics_are_none_without_phases_or_spans():
    """A trace with no phase (the CPU here, or a program without the
    scopes) and a snapshot without the spans read nothing, never 0."""
    ctx = _ctx(HAND, hists=[("stream.refit.lasso_iters", 1, 100),
                            ("stream.refit.debias_iters", 1, 150)])
    for name in ("refit.msolve_ms", "refit.lasso_ms", "msolve_roofline",
                 "front.queue_ms", "service.ingest_ms"):
        assert harness.read_metric(name, ctx) is None, name
    unscoped = {**HAND, "devices": [dict(HAND["devices"][0], phases=[])]}
    assert harness.read_metric("refit.msolve_ms", _ctx(unscoped)) is None


def test_idle_gaps_named_by_program_spans_by_hand():
    gaps = trace.idle_gaps(HAND)
    # idle: [8, 10] the predict batch, [0, 1] nothing open, [6, 7] the
    # ingest and its fold child open at 6.5
    assert gaps == [["serve.batch", 2.0], ["no bench span", 1.0],
                    ["bench.ingest+stream.ingest+stream.ingest.fold", 1.0]]


def test_program_op_names_match_a_cpu_profile(tmp_path):
    """The premise of the phase map, on the CPU: every op a profile of
    the program's warm refit shows is an instruction of the refit
    compiled again from the cell's shapes, and the ops with a phase
    cover every phase."""
    import jax
    from jax.profiler import ProfileData

    from chipbench.check import penalties
    from repro.stream.refit import refit
    from repro.stream.state import init_stream_state
    _, _, cfg, _ = tiny_spec("tenants.refit")
    s = cfg["service"]
    lam, mu, Lam = penalties(cfg)
    state = jax.tree.map(jax.numpy.asarray,
                         init_stream_state(cfg["m"], cfg["p"]))
    args = dict(lasso_iters=s["warm_lasso_iters"],
                debias_iters=s["warm_debias_iters"], warm=True,
                tol=s["refit_tol"])
    jax.block_until_ready(refit(state, lam, mu, Lam, **args))
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(refit(state, lam, mu, Lam, **args))
        time.sleep(0.1)
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    host = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    ran = {ev.name for line in host.lines for ev in line.events
           if dict(ev.stats).get("hlo_module") == "jit_refit"}
    op_phase = phases.program_op_phases(cfg)
    assert ran and ran <= set(op_phase)
    assert {op_phase[n] for n in ran} >= set(phases.PHASES)
