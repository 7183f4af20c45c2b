"""Online DSML over a non-stationary stream: ingest minibatches, let the
drift-aware service decide when to refit, and watch it re-acquire the
support after a mid-stream regime shift.

    PYTHONPATH=src python examples/stream_online.py [--smoke] [--decay 0.7]

With multiple devices (e.g. XLA_FLAGS=--xla_force_host_platform_device_count=8)
ingestion runs SPMD over a data x task mesh via `stream.accumulate`.
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import ar_covariance, hamming, sample_coefficients
from repro.stream import StreamingDsmlService


def make_regime(key, p, m, s, rho=0.5):
    Sigma = ar_covariance(p, rho)
    chol = jnp.linalg.cholesky(Sigma + 1e-9 * jnp.eye(p))
    B, support = sample_coefficients(key, p, m, s, low=0.3, high=1.0)
    return chol, B, support


def draw_chunk(key, chol, B, n, sigma=1.0):
    m = B.shape[1]
    p = B.shape[0]
    k_x, k_e = jax.random.split(key)
    Xs = jax.random.normal(k_x, (m, n, p)) @ chol.T
    ys = jnp.einsum("tnp,pt->tn", Xs, B) + sigma * jax.random.normal(k_e, (m, n))
    return Xs, ys


def main(argv=None):
    """Run the stream demo; returns the headline metrics dict so the
    golden-band smoke test (tests/test_figures_smoke.py) can pin them —
    same `--smoke` + committed-band pattern as the figure drivers."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--m", type=int, default=8)
    ap.add_argument("--p", type=int, default=128)
    ap.add_argument("--s", type=int, default=8)
    ap.add_argument("--chunk-size", type=int, default=256)
    ap.add_argument("--chunks", type=int, default=16)
    ap.add_argument("--decay", type=float, default=0.7,
                    help="exponential forgetting per chunk (1.0 = none)")
    ap.add_argument("--shift-at", type=float, default=0.5,
                    help="fraction of the stream after which the true "
                         "support moves")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI")
    ap.add_argument("--obs-out", default=None, metavar="PATH",
                    help="write the telemetry snapshot (and a "
                         ".trace.json Chrome trace next to it)")
    args = ap.parse_args(argv)
    if args.smoke:
        args.m, args.p, args.s = 4, 48, 5
        args.chunk_size, args.chunks = 64, 8

    base = float(jnp.sqrt(jnp.log(float(args.p)) / args.chunk_size))
    mesh = None
    if jax.device_count() > 1 and args.m % 2 == 0 \
            and args.chunk_size % (jax.device_count() // 2) == 0:
        from repro.substrate import data_task_mesh
        mesh = data_task_mesh(n_task=2)
        print(f"ingesting SPMD over mesh {dict(mesh.shape)}")

    svc = StreamingDsmlService(
        args.m, args.p, lam=4 * base, mu=base, Lam=1.0,
        decay=args.decay, refit_every=2 * args.chunk_size,
        lasso_iters=400, debias_iters=400, chunk_n=args.chunk_size,
        mesh=mesh)

    key = jax.random.PRNGKey(0)
    k_a, k_b, key = jax.random.split(key, 3)
    chol, B, support = make_regime(k_a, args.p, args.m, args.s)
    shift_chunk = int(args.shift_at * args.chunks)
    print(f"stream: m={args.m} tasks, p={args.p}, s={args.s}, "
          f"{args.chunks} chunks x {args.chunk_size} samples, "
          f"decay={args.decay}, shift at chunk {shift_chunk}")

    refits_during_stream = 0
    for i in range(args.chunks):
        if i == shift_chunk:
            chol, B, support = make_regime(k_b, args.p, args.m, args.s)
            print(f"--- regime shift at chunk {i}: new support ---")
        key, k = jax.random.split(key)
        Xs, ys = draw_chunk(k, chol, B, args.chunk_size)
        t0 = time.perf_counter()
        info = svc.ingest(Xs, ys)
        dt = (time.perf_counter() - t0) * 1e3
        if info is not None:
            h = int(hamming(svc.state.support, support))
            err = float(jnp.max(jnp.abs(svc.state.beta_tilde - B.T)))
            refits_during_stream += 1
            print(f"[chunk {i:3d} | eff samples {svc.samples_seen:7.0f}] "
                  f"refit gen={int(info.generation)} |S|={int(info.support_size)} "
                  f"jaccard={float(info.jaccard):.2f} hamming={h} "
                  f"est_err={err:.3f} ({dt:.0f} ms incl. ingest)")

    svc.refit()
    h = int(hamming(svc.state.support, support))
    err = float(jnp.max(jnp.abs(svc.state.beta_tilde - B.T)))
    # serve one scoring round so the trace timeline shows the full
    # ingest -> refit -> predict lifecycle of the service
    jax.block_until_ready(svc.predict(Xs))
    print(f"final: generation {svc.generation}, support hamming vs current "
          f"regime = {h} (decay {'forgets' if args.decay < 1 else 'keeps'} "
          f"the old regime)")

    # telemetry-derived headlines (None-safe: REPRO_OBS=0 zeroes them)
    ing = obs.hist_stats("stream.ingest.ms")
    ref_ms = obs.hist_stats("stream.refit.ms")
    ing_rows = obs.counter_total("stream.ingest.rows")
    obs_rate = (ing_rows / (ing["sum"] * 1e-3)
                if ing and ing["sum"] > 0 else 0.0)
    if args.obs_out:
        from repro.obs import export as obs_export
        obs_export.write_snapshot(
            args.obs_out,
            meta={"example": "stream_online", "smoke": bool(args.smoke)})
        base = args.obs_out[:-5] if args.obs_out.endswith(".json") \
            else args.obs_out
        obs_export.write_chrome_trace(base + ".trace.json")
        print(f"wrote {args.obs_out} and {base}.trace.json")
    return {
        "final_hamming": h,
        "final_est_err": err,
        "generations": int(svc.generation),
        "refits_during_stream": refits_during_stream,
        "samples_seen": float(svc.samples_seen),
        "obs_ingest_rows_per_s": obs_rate,
        "obs_refit_latency_ms": ref_ms["mean"] if ref_ms else 0.0,
        "obs_refits_recorded": ref_ms["count"] if ref_ms else 0,
    }


if __name__ == "__main__":
    from repro.substrate import enable_compile_cache
    enable_compile_cache()
    main()
