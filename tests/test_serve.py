"""Serving-front correctness: atomic generation swaps, the microbatch
admission layer, the predict input contract, and the substrate feed.

The concurrency claims are tested the only way that means anything —
with real threads hammering predict while ingest/refit adopt new
generations — and verified bitwise: every observed (scores, generation)
pair must reproduce exactly from that generation's recorded model under
the same dispatch shape, so a torn or mixed-generation read cannot hide
inside a tolerance.
"""
import os
import threading
import time
from concurrent.futures import Future

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.obs.registry import Registry, _quantile
from repro.stream import (
    ModelGeneration, ServingFront, StreamingDsmlService, bucket_rows,
    init_stream_state, ingest,
)
from repro.stream.serve import _Request
from repro.stream.service import _predict_shared
from repro.substrate import data_task_mesh, feed_chunk, feed_shards

LAM, MU, THR = 0.05, 0.1, 0.02
M, P, CHUNK = 4, 32, 128


def _service(**kw):
    kw.setdefault("refit_every", CHUNK)
    kw.setdefault("lasso_iters", 150)
    kw.setdefault("debias_iters", 150)
    kw.setdefault("refit_tol", 1e-5)
    kw.setdefault("guard", False)
    return StreamingDsmlService(M, P, lam=LAM, mu=MU, Lam=THR, **kw)


def _chunk(rng, n=CHUNK):
    X = rng.standard_normal((M, n, P)).astype(np.float32)
    w = rng.standard_normal((M, P)).astype(np.float32) / np.sqrt(P)
    y = (np.einsum("tnp,tp->tn", X, w)
         + 0.05 * rng.standard_normal((M, n))).astype(np.float32)
    return jnp.asarray(X), jnp.asarray(y)


def _reference(beta_np, X):
    """The verification oracle: the SAME jitted dispatch at the SAME
    shapes on that generation's recorded weights — bitwise equal to
    what serving must have computed if (and only if) it read one
    coherent snapshot."""
    return np.asarray(_predict_shared(jnp.asarray(beta_np), X))


# -- units ----------------------------------------------------------------

def test_bucket_rows_powers_of_two():
    assert [bucket_rows(r) for r in (1, 7, 8, 9, 63, 64, 65)] == \
        [8, 8, 8, 16, 64, 64, 128]
    assert bucket_rows(3, min_bucket=4) == 4
    with pytest.raises(ValueError):
        bucket_rows(0)


def test_obs_quantiles():
    assert _quantile([5.0], 0.99) == 5.0
    vals = sorted(float(v) for v in range(1, 101))
    assert _quantile(vals, 0.5) == pytest.approx(50.5)
    assert _quantile(vals, 0.99) == pytest.approx(99.01)
    reg = Registry()
    for v in range(1, 101):
        reg.observe("lat.ms", float(v), route="a" if v % 2 else "b")
    q = reg.hist_quantiles("lat.ms")
    assert q[0.5] == pytest.approx(50.5)
    assert q[0.99] == pytest.approx(99.01)
    assert reg.hist_quantiles("lat.ms", route="a")[0.5] == pytest.approx(50.0)
    assert reg.hist_quantiles("missing") is None
    snap = reg.snapshot()
    hist = [h for h in snap["histograms"] if h["labels"] == {"route": "a"}][0]
    assert hist["p50"] == pytest.approx(50.0)
    assert "p99" in hist


def test_disabled_registry_retains_nothing():
    reg = Registry(enabled=False)
    reg.observe("lat.ms", 1.0)
    assert reg.hist_quantiles("lat.ms") is None
    assert reg.snapshot()["histograms"] == []


# -- predict contract -----------------------------------------------------

def test_predict_rank1_is_one_shared_row():
    svc = _service()
    rng = np.random.default_rng(0)
    svc.ingest(*_chunk(rng))
    row = rng.standard_normal(P).astype(np.float32)
    out = svc.predict(row)
    assert out.shape == (M, 1)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(svc.predict(row.reshape(1, P))))


def test_predict_rows_counter_counts_normalized_rows():
    svc = _service()
    rng = np.random.default_rng(1)
    svc.ingest(*_chunk(rng))
    before = obs.counter_total("stream.predict.rows")
    svc.predict(rng.standard_normal(P).astype(np.float32))
    assert obs.counter_total("stream.predict.rows") - before == 1  # not P
    svc.predict(rng.standard_normal((5, P)).astype(np.float32))
    svc.predict(rng.standard_normal((M, 3, P)).astype(np.float32))
    assert obs.counter_total("stream.predict.rows") - before == 1 + 5 + 3


def test_predict_rejects_malformed_inputs():
    svc = _service()
    rng = np.random.default_rng(2)
    for bad in (rng.standard_normal(P + 1),
                rng.standard_normal((5, P + 1)),
                rng.standard_normal((M + 1, 5, P)),
                rng.standard_normal((M, 5, P + 1)),
                rng.standard_normal((2, 2, 2, 2))):
        with pytest.raises(ValueError):
            svc.predict(bad.astype(np.float32))


# -- generation snapshots -------------------------------------------------

def test_snapshot_survives_adoption_and_publish_sites():
    svc = _service()
    rng = np.random.default_rng(3)
    held = svc.serving()
    assert isinstance(held, ModelGeneration) and held.generation == 0
    held_beta = np.asarray(held.beta_tilde)
    svc.ingest(*_chunk(rng))                       # triggers a refit
    assert svc.generation == 1
    assert svc.serving().generation == 1
    # the snapshot captured before adoption is untouched
    assert held.generation == 0
    np.testing.assert_array_equal(np.asarray(held.beta_tilde), held_beta)


def test_restore_republishes(tmp_path):
    svc = _service(ckpt_dir=str(tmp_path))
    rng = np.random.default_rng(4)
    svc.ingest(*_chunk(rng))
    fitted = np.asarray(svc.serving().beta_tilde)
    assert svc.serving().generation == 1
    fresh = _service(ckpt_dir=str(tmp_path))
    assert fresh.serving().generation == 0
    fresh.restore()
    assert fresh.serving().generation == 1
    np.testing.assert_array_equal(np.asarray(fresh.serving().beta_tilde),
                                  fitted)


def test_ingest_while_predict_interleaving_bitwise():
    """Predictions taken between chunk folds must equal post-hoc
    predictions from the same generation's model, bitwise."""
    svc = _service()
    rng = np.random.default_rng(5)
    X0 = jnp.asarray(rng.standard_normal((6, P)).astype(np.float32))
    betas = {0: np.asarray(svc.serving().beta_tilde)}
    observed = []
    for _ in range(6):
        scores, gen = svc.predict(X0, return_generation=True)
        observed.append((np.asarray(scores), gen))
        svc.ingest(*_chunk(rng))
        snap = svc.serving()
        betas[snap.generation] = np.asarray(snap.beta_tilde)
    assert svc.generation >= 3        # refits really happened mid-stream
    for scores, gen in observed:
        np.testing.assert_array_equal(scores, _reference(betas[gen], X0))


def test_threaded_generation_swap_stress():
    """Predict hammered from threads while ingest adopts generation
    after generation: every observed (scores, generation) pair must
    reproduce bitwise from that generation's model — a torn read of a
    half-swapped model cannot produce a score vector that matches any
    single generation. Generations must also be nondecreasing per
    thread (a reader can lag the swap, never un-see it)."""
    svc = _service(max_refit_interval=CHUNK)       # adopt every chunk
    rng = np.random.default_rng(6)
    X0 = jnp.asarray(rng.standard_normal((4, P)).astype(np.float32))
    svc.predict(X0)                                # compile before racing
    betas = {0: np.asarray(svc.serving().beta_tilde)}
    chunks = [_chunk(rng) for _ in range(12)]
    done = threading.Event()
    results, errors = [], []
    lock = threading.Lock()

    def ingest_loop():
        try:
            for X, y in chunks:
                svc.ingest(X, y)
                snap = svc.serving()
                betas[snap.generation] = np.asarray(snap.beta_tilde)
        finally:
            done.set()

    def predict_loop():
        mine = []
        try:
            while not done.is_set():
                scores, gen = svc.predict(X0, return_generation=True)
                mine.append((np.asarray(scores), gen))
        except Exception as e:  # noqa: BLE001 - surfaced to the assert
            errors.append(e)
        with lock:
            results.append(mine)

    workers = [threading.Thread(target=predict_loop) for _ in range(4)]
    for t in workers:
        t.start()
    feeder = threading.Thread(target=ingest_loop)
    feeder.start()
    feeder.join()
    for t in workers:
        t.join()

    assert not errors, errors
    assert svc.generation == len(chunks)
    total = 0
    refs = {}
    for mine in results:
        gens = [g for _, g in mine]
        assert gens == sorted(gens)               # never un-adopts
        for scores, gen in mine:
            assert gen in betas
            if gen not in refs:
                refs[gen] = _reference(betas[gen], X0)
            np.testing.assert_array_equal(scores, refs[gen])
            total += 1
    assert total > 0


# -- the microbatch front -------------------------------------------------

def test_front_process_single_dispatch_parity():
    """_process on hand-built requests (no threads): one padded
    dispatch, per-request slices bitwise equal to scoring the padded
    batch directly, one shared generation stamp."""
    svc = _service()
    rng = np.random.default_rng(7)
    svc.ingest(*_chunk(rng))
    front = ServingFront(svc, max_batch=16)
    rows = [rng.standard_normal((n, P)).astype(np.float32)
            for n in (1, 3, 2)]
    reqs = [_Request(x, Future(), time.perf_counter()) for x in rows]
    front._process(reqs)

    padded = np.zeros((bucket_rows(6), P), np.float32)
    padded[:1], padded[1:4], padded[4:6] = rows[0], rows[1], rows[2]
    snap = svc.serving()
    expect = _reference(np.asarray(snap.beta_tilde), jnp.asarray(padded))
    off = 0
    for req, x in zip(reqs, rows):
        res = req.future.result(timeout=1)
        assert res.generation == snap.generation
        np.testing.assert_array_equal(res.scores,
                                      expect[:, off:off + x.shape[0]])
        off += x.shape[0]


def test_front_queue_wait_one_per_request_within_its_latency():
    """`serve.queue_ms` (submit to the batch's dispatch) is observed once
    per request, and never exceeds that request's `serve.request_ms`:
    within a batch both share one dispatch, so each request's latency
    less its queue wait is the same non-negative batch time."""
    svc = _service()
    rng = np.random.default_rng(11)
    svc.ingest(*_chunk(rng))
    front = ServingFront(svc, max_batch=16)
    for ages_ms in ((50.0,), (30.0, 20.0, 5.0), (1.0, 0.0)):
        obs.reset()
        now = time.perf_counter()
        reqs = [_Request(rng.standard_normal((1, P)).astype(np.float32),
                         Future(), now - a / 1e3) for a in ages_ms]
        front._process(reqs)
        queue = obs.hist_stats("serve.queue_ms")
        total = obs.hist_stats("serve.request_ms")
        assert queue["count"] == total["count"] == len(reqs)
        assert queue["max"] >= max(ages_ms)
        batch_ms = total["max"] - queue["max"]
        assert batch_ms > 0             # the dispatch and its wait
        assert total["min"] - queue["min"] == pytest.approx(batch_ms,
                                                            abs=1e-6)
        assert total["sum"] - queue["sum"] == pytest.approx(
            len(reqs) * batch_ms, abs=1e-6)
    obs.reset()


def test_front_threaded_serving_during_ingest():
    """Threaded smoke: submits race a live ingest/refit loop; every
    result's generation is a real published generation and its scores
    match that generation's model (allclose — the padded bucket shape
    varies with batch fill, which legitimately changes reduction
    order)."""
    svc = _service()
    rng = np.random.default_rng(8)
    betas = {0: np.asarray(svc.serving().beta_tilde)}
    chunks = [_chunk(rng) for _ in range(6)]
    row = rng.standard_normal(P).astype(np.float32)
    with ServingFront(svc, max_batch=8, max_delay_ms=1.0) as front:
        front.predict(row, timeout=10)             # compile before racing
        done = threading.Event()

        def ingest_loop():
            try:
                for X, y in chunks:
                    svc.ingest(X, y)
                    snap = svc.serving()
                    betas[snap.generation] = np.asarray(snap.beta_tilde)
            finally:
                done.set()

        feeder = threading.Thread(target=ingest_loop)
        feeder.start()
        futs = []
        while not done.is_set():
            futs.append(front.submit(row))
            time.sleep(0.001)
        feeder.join()
        res = [f.result(timeout=10) for f in futs]

    assert svc.generation >= 3
    for r in res:
        assert r.generation in betas
        want = betas[r.generation] @ row           # (m,) float32 einsum
        np.testing.assert_allclose(r.scores[:, 0], want, atol=1e-4)


def test_front_submit_validation_and_stop():
    svc = _service()
    front = ServingFront(svc, max_batch=4)
    with pytest.raises(RuntimeError):              # not started
        front.submit(np.zeros(P, np.float32))
    front.start()
    with pytest.raises(ValueError):                # wrong feature count
        front.submit(np.zeros(P + 1, np.float32))
    with pytest.raises(ValueError):                # oversized block
        front.submit(np.zeros((5, P), np.float32))
    fut = front.submit(np.zeros(P, np.float32))
    assert fut.result(timeout=10).scores.shape == (M, 1)
    front.stop()
    with pytest.raises(RuntimeError):              # stopped
        front.submit(np.zeros(P, np.float32))


@pytest.mark.serve_perf
@pytest.mark.skipif(not os.environ.get("REPRO_SERVE_PERF"),
                    reason="set REPRO_SERVE_PERF=1 for the latency smoke")
def test_front_p99_latency_smoke():
    """Opt-in latency gate: a loaded front must hold a loose p99 (the
    committed regression floor lives in benchmarks/check_regression.py;
    this is the in-tree canary)."""
    svc = _service()
    rng = np.random.default_rng(9)
    svc.ingest(*_chunk(rng))
    row = rng.standard_normal(P).astype(np.float32)
    with ServingFront(svc, max_batch=32, max_delay_ms=1.0) as front:
        front.predict(row, timeout=10)
        futs = [front.submit(row) for _ in range(400)]
        for f in futs:
            f.result(timeout=30)
        q = front.latency_quantiles()
    assert q is not None and q[0.99] < 250.0, q


# -- the substrate feed ---------------------------------------------------

def test_feed_chunk_matches_host_ingest():
    n_dev = len(jax.devices())
    n_task = 2 if n_dev >= 2 else 1
    n_data = next((d for d in (4, 2, 1)
                   if n_dev // n_task >= d and CHUNK % d == 0), 1)
    mesh = data_task_mesh(n_task=n_task, n_data=n_data)
    rng = np.random.default_rng(10)
    X, y = _chunk(rng)
    host = ingest(init_stream_state(M, P), X, y)
    svc = _service(mesh=mesh)
    svc._interval = 10 ** 9                        # fold only, no refit
    svc.ingest(X, y)
    np.testing.assert_allclose(np.asarray(svc.state.Sigmas),
                               np.asarray(host.Sigmas), atol=1e-4)
    np.testing.assert_allclose(np.asarray(svc.state.cs),
                               np.asarray(host.cs), atol=1e-5)


def test_feed_shards_equals_feed_chunk():
    """The per-worker assembly path must produce the same global array
    (values AND sharding) as the single-controller placement."""
    n_dev = len(jax.devices())
    n_task = 2 if n_dev >= 2 else 1
    n_data = 2 if n_dev >= 4 else 1
    mesh = data_task_mesh(n_task=n_task, n_data=n_data)
    rng = np.random.default_rng(11)
    X, y = _chunk(rng, n=64)
    Xc, yc = feed_chunk(X, y, mesh)
    blocks = np.split(np.asarray(X), n_data, axis=1)
    yblocks = np.split(np.asarray(y), n_data, axis=1)
    Xs, ys = feed_shards(blocks, yblocks, mesh)
    np.testing.assert_array_equal(np.asarray(Xs), np.asarray(Xc))
    np.testing.assert_array_equal(np.asarray(ys), np.asarray(yc))
    assert Xs.sharding.is_equivalent_to(Xc.sharding, X.ndim)
    with pytest.raises(ValueError):                # wrong block count
        feed_shards(blocks[:1] * (n_data + 1), yblocks[:1] * (n_data + 1),
                    mesh)
