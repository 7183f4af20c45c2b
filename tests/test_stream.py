"""Streaming DSML subsystem tests.

Contract: sufficient statistics are additive, so (a) ingesting a
dataset in ANY chunking and refitting reproduces `dsml_fit` on the
concatenated data; (b) a warm-started refit on unchanged statistics is
a fixed point; (c) the sharded data x task accumulator equals the host
path; (d) decay and window variants match their closed forms; (e) the
service drives ingest/refit/predict/save/load coherently.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dsml_fit, gen_regression, sufficient_stats
from repro.stream import (
    StreamingDsmlService, ingest, init_stream_state, init_window, merge,
    refit, window_ingest, window_stats,
)
from repro.substrate import run_probe

LAM, MU, THR = 0.4, 0.2, 1.0
ITERS = dict(lasso_iters=200, debias_iters=200)


def _data(m=4, n=120, p=48, s=5, seed=0):
    return gen_regression(jax.random.PRNGKey(seed), m=m, n=n, p=p, s=s)


def _chunks(data, k):
    return zip(jnp.split(data.Xs, k, axis=1), jnp.split(data.ys, k, axis=1))


def _ingest_all(data, k, **kw):
    state = init_stream_state(data.Xs.shape[0], data.Xs.shape[2])
    for Xc, yc in _chunks(data, k):
        state = ingest(state, Xc, yc, **kw)
    return state


# ---------------------------------------------------------------------------
# additivity: chunked ingest == one-shot statistics == dsml_fit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 3, 8])
def test_chunked_ingest_matches_one_shot_stats(k):
    data = _data()
    state = _ingest_all(data, k)
    S, c = sufficient_stats(data.Xs, data.ys)
    np.testing.assert_allclose(np.asarray(state.Sigmas), np.asarray(S),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(state.cs), np.asarray(c),
                               atol=1e-5)
    assert float(state.counts[0]) == data.Xs.shape[1]


@pytest.mark.parametrize("k", [1, 3, 8])
def test_stream_refit_reproduces_dsml_fit(k):
    """The acceptance bar: ingest in k chunks, refit once, and get the
    batch `dsml_fit` answer on the concatenated data to <= 1e-5."""
    data = _data()
    state, info = refit(_ingest_all(data, k), LAM, MU, THR, **ITERS)
    ref = dsml_fit(data.Xs, data.ys, LAM, MU, THR, **ITERS)
    np.testing.assert_allclose(np.asarray(state.beta_tilde),
                               np.asarray(ref.beta_tilde), atol=1e-5)
    np.testing.assert_allclose(np.asarray(state.beta_u),
                               np.asarray(ref.beta_u), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(state.support),
                                  np.asarray(ref.support))
    assert int(info.generation) == 1


def test_warm_refit_on_unchanged_stats_is_fixed_point():
    data = _data()
    state, _ = refit(_ingest_all(data, 3), LAM, MU, THR, **ITERS)
    again, info = refit(state, LAM, MU, THR, **ITERS)
    np.testing.assert_allclose(np.asarray(again.beta_tilde),
                               np.asarray(state.beta_tilde), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(again.support),
                                  np.asarray(state.support))
    assert float(info.jaccard) == 1.0
    assert int(again.generation) == 2


def test_merge_matches_single_stream():
    data = _data()
    Xa, Xb = jnp.split(data.Xs, 2, axis=1)
    ya, yb = jnp.split(data.ys, 2, axis=1)
    m, p = data.Xs.shape[0], data.Xs.shape[2]
    a = ingest(init_stream_state(m, p), Xa, ya)
    b = ingest(init_stream_state(m, p), Xb, yb)
    both = merge(a, b)
    S, c = sufficient_stats(data.Xs, data.ys)
    np.testing.assert_allclose(np.asarray(both.Sigmas), np.asarray(S),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(both.cs), np.asarray(c), atol=1e-5)


# ---------------------------------------------------------------------------
# non-stationary variants
# ---------------------------------------------------------------------------

def test_decayed_ingest_matches_closed_form():
    """With per-chunk decay d, the state must equal the weighted average
    sum_k d^{K-k} n_k stats_k / sum_k d^{K-k} n_k."""
    data = _data()
    d, k = 0.5, 4
    state = _ingest_all(data, k, decay=d)
    chunks = list(_chunks(data, k))
    w = jnp.asarray([d ** (k - 1 - i) for i in range(k)])
    num_S, num_c, den = 0.0, 0.0, 0.0
    for wi, (Xc, yc) in zip(w, chunks):
        S, c = sufficient_stats(Xc, yc)
        n = Xc.shape[1]
        num_S, num_c, den = num_S + wi * n * S, num_c + wi * n * c, den + wi * n
    np.testing.assert_allclose(np.asarray(state.Sigmas),
                               np.asarray(num_S / den), atol=1e-5)
    np.testing.assert_allclose(np.asarray(state.cs),
                               np.asarray(num_c / den), atol=1e-5)
    np.testing.assert_allclose(float(state.counts[0]), float(den), rtol=1e-6)


def test_weighted_ingest_matches_manual_weighting():
    data = _data(m=2, n=40, p=16, s=3)
    w = jax.random.uniform(jax.random.PRNGKey(3), data.ys.shape,
                           minval=0.2, maxval=1.0)
    state = ingest(init_stream_state(2, 16), data.Xs, data.ys, weights=w)
    Xw = data.Xs * w[..., None]
    S = jnp.einsum("tni,tnj->tij", Xw, data.Xs) / jnp.sum(w, 1)[:, None, None]
    np.testing.assert_allclose(np.asarray(state.Sigmas), np.asarray(S),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(state.counts),
                               np.asarray(jnp.sum(w, 1)), rtol=1e-5)


def test_window_stats_cover_exactly_last_w_chunks():
    data = _data()
    k, w = 6, 3
    win = init_window(w, data.Xs.shape[0], data.Xs.shape[2])
    chunks = list(_chunks(data, k))
    for Xc, yc in chunks:
        win = window_ingest(win, Xc, yc)
    X_tail = jnp.concatenate([Xc for Xc, _ in chunks[-w:]], axis=1)
    y_tail = jnp.concatenate([yc for _, yc in chunks[-w:]], axis=1)
    S, c, counts = window_stats(win)
    S_ref, c_ref = sufficient_stats(X_tail, y_tail)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(c), np.asarray(c_ref), atol=1e-5)
    assert float(counts[0]) == X_tail.shape[1]
    assert int(win.seen) == k


# ---------------------------------------------------------------------------
# sharded accumulation (engine-level SPMD)
# ---------------------------------------------------------------------------

def test_sharded_ingest_matches_host_single_device():
    from repro.stream import ingest_sharded
    from repro.substrate import data_task_mesh
    mesh = data_task_mesh(n_task=1, n_data=1)
    data = _data()
    host = _ingest_all(data, 2)
    shard = init_stream_state(data.Xs.shape[0], data.Xs.shape[2])
    for Xc, yc in _chunks(data, 2):
        shard = ingest_sharded(shard, Xc, yc, mesh)
    np.testing.assert_allclose(np.asarray(host.Sigmas),
                               np.asarray(shard.Sigmas), atol=1e-5)
    np.testing.assert_allclose(np.asarray(host.cs), np.asarray(shard.cs),
                               atol=1e-5)


REFIT_SCOPES = ("refit.power", "refit.lasso", "refit.msolve",
                "refit.debias", "refit.threshold")


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["one_device", "shard_map"])
def test_refit_phases_are_named_scopes_in_the_compiled_program(sharded):
    """Every phase of the refit reaches the compiled program's op
    metadata under its own scope, on the single-device and the
    shard_map path, so a device profile can be split by phase."""
    from repro.stream.refit import refit as refit_fn
    from repro.substrate import data_task_mesh
    state = jax.eval_shape(lambda: init_stream_state(4, 16))
    mesh = {"mesh": data_task_mesh(n_task=1, n_data=1)} if sharded else {}
    text = refit_fn.lower(state, LAM, MU, THR, lasso_iters=8,
                          debias_iters=8, warm=True, tol=1e-5,
                          **mesh).compile().as_text()
    for scope in REFIT_SCOPES:
        assert re.search(rf'op_name="[^"]*/{re.escape(scope)}/', text), scope


_MESH8 = r"""
import jax, numpy as np
import jax.numpy as jnp
from repro.core import dsml_fit, gen_regression
from repro.stream import ingest_sharded, init_stream_state, refit
from repro.substrate import data_task_mesh

mesh = data_task_mesh(n_task=2)            # 8 devices -> (4 data, 2 task)
data = gen_regression(jax.random.PRNGKey(1), m=4, n=160, p=48, s=5)
state = init_stream_state(4, 48)
for Xc, yc in zip(jnp.split(data.Xs, 4, axis=1), jnp.split(data.ys, 4, axis=1)):
    state = ingest_sharded(state, Xc, yc, mesh)
state, _ = refit(state, 0.4, 0.2, 1.0, lasso_iters=200, debias_iters=200)
ref = dsml_fit(data.Xs, data.ys, 0.4, 0.2, 1.0, lasso_iters=200,
               debias_iters=200)
err = float(np.max(np.abs(np.asarray(state.beta_tilde) -
                          np.asarray(ref.beta_tilde))))
sup_eq = bool(np.all(np.asarray(state.support) == np.asarray(ref.support)))
print(f"RESULT err={err} sup_eq={sup_eq}")
"""


def test_sharded_ingest_refit_matches_dsml_eight_devices():
    """Chunked SPMD ingest over a (4 data x 2 task) mesh, then refit,
    must reproduce `dsml_fit` on the concatenated data to <= 1e-5."""
    res = run_probe(_MESH8, n_devices=8, timeout=900)
    assert res.returncode == 0, res.stderr[-2000:]
    m = re.search(r"RESULT err=([\d.e+-]+) sup_eq=(\w+)", res.stdout)
    assert m, res.stdout
    assert float(m.group(1)) < 1e-5
    assert m.group(2) == "True"


# ---------------------------------------------------------------------------
# service driver
# ---------------------------------------------------------------------------

def test_service_ingest_refit_predict_roundtrip(tmp_path):
    data = _data()
    svc = StreamingDsmlService(4, 48, lam=LAM, mu=MU, Lam=THR,
                               refit_every=60, lasso_iters=200,
                               debias_iters=200)
    infos = [svc.ingest(Xc, yc) for Xc, yc in _chunks(data, 4)]
    assert svc.generation >= 1                     # cadence fired
    assert any(i is not None for i in infos)
    assert svc.samples_seen == data.Xs.shape[1]
    pred = svc.predict(data.Xs)
    assert pred.shape == data.ys.shape
    assert bool(jnp.all(jnp.isfinite(pred)))
    shared = svc.predict(data.Xs[0])               # shared-design scoring
    assert shared.shape == (4, data.Xs.shape[1])

    path = str(tmp_path / "stream_state")
    svc.save(path)
    fresh = StreamingDsmlService(4, 48, lam=LAM, mu=MU, Lam=THR)
    fresh.load(path)
    assert fresh.generation == svc.generation
    np.testing.assert_array_equal(np.asarray(fresh.predict(data.Xs)),
                                  np.asarray(pred))


def test_service_window_mode_survives_save_load():
    """A restored window-mode service must keep serving the same model:
    the ring buffer round-trips with the state, and a refit right after
    restore must NOT wipe the statistics."""
    data = _data()
    svc = StreamingDsmlService(4, 48, lam=LAM, mu=MU, Lam=THR, window=3,
                               refit_every=60, lasso_iters=200,
                               debias_iters=200)
    for Xc, yc in _chunks(data, 4):
        svc.ingest(Xc, yc)
    assert svc.generation >= 1
    before = np.asarray(svc.state.beta_tilde)
    assert np.abs(before).max() > 0

    import tempfile, os
    path = os.path.join(tempfile.mkdtemp(), "win_state")
    svc.save(path)
    fresh = StreamingDsmlService(4, 48, lam=LAM, mu=MU, Lam=THR, window=3,
                                 refit_every=60, lasso_iters=200,
                                 debias_iters=200)
    fresh.load(path)
    assert int(fresh.window.seen) == int(svc.window.seen)
    fresh.refit()
    assert np.abs(np.asarray(fresh.state.beta_tilde)).max() > 0
    assert float(jnp.max(jnp.abs(fresh.state.Sigmas))) > 0

    # a refit on a NEVER-fed window service must not zero the stats
    empty = StreamingDsmlService(4, 48, lam=LAM, mu=MU, Lam=THR, window=3)
    empty.state = svc.state
    empty.refit()
    assert float(jnp.max(jnp.abs(empty.state.Sigmas))) > 0


def test_service_rejects_decay_with_window():
    with pytest.raises(ValueError):
        StreamingDsmlService(2, 8, lam=LAM, mu=MU, Lam=THR,
                             window=2, decay=0.9)


def test_service_rejects_window_ckpt_in_plain_service(tmp_path):
    """A window-mode checkpoint must not silently load as cumulative."""
    svc = StreamingDsmlService(2, 8, lam=LAM, mu=MU, Lam=THR, window=2)
    path = str(tmp_path / "win_ckpt")
    svc.save(path)
    plain = StreamingDsmlService(2, 8, lam=LAM, mu=MU, Lam=THR)
    with pytest.raises(ValueError):
        plain.load(path)


def test_service_widens_refit_interval_when_support_stable():
    data = _data(n=240)
    svc = StreamingDsmlService(4, 48, lam=LAM, mu=MU, Lam=THR,
                               refit_every=40, drift_threshold=0.05,
                               lasso_iters=200, debias_iters=200,
                               warm_lasso_iters=200)
    for Xc, yc in _chunks(data, 6):
        svc.ingest(Xc, yc)
    # identical-distribution traffic: once warm, supports stop moving and
    # the adaptive cadence must have backed off from the base interval.
    assert svc.generation >= 2
    assert svc._interval > svc.refit_every
    assert float(svc.last_info.jaccard) == 1.0
