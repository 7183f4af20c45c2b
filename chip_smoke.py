#!/usr/bin/env python3
"""Bring the streaming DSML service up on a TPU, end to end.

    python chip_smoke.py                # one chip: ingest -> refit -> serve
    python chip_smoke.py --chips 4      # the sharded service on 4 chips

One chip: a many-tenant deployment at moderate width. m=128 tasks in
p=1024 features with a shared support of s=16 (AR(0.5) design, unit
Gaussian noise, coefficients U(0.3, 1) on the support, all generated on
the device from --seed) arrive as 6 chunks of 512 rows per task.
`StreamingDsmlService` folds them and refits every 1024 rows (one cold
refit, two warm ones); while the last two chunks arrive, a
`ServingFront` answers predict requests from 4 client threads. The run
is checked against plain references:

* the running statistics against an einsum over every ingested row at
  `precision=HIGHEST`;
* the final support against the ground truth, and the final model
  against a cold fit on the same statistics with every Pallas kernel
  replaced by its jnp oracle, at `jax.default_matmul_precision
  ("highest")`;
* every served score against a float64 host product with the
  `beta_tilde` of the generation that the response names;
* the `dispatch.route` counters: no `rank_update` or FISTA-step shape
  may have fallen back to its oracle.

`--chips 4` runs only the sharded path: the same data and schedule
through `StreamingDsmlService(mesh=data_task_mesh(n_task=2))` (data=2 x
task=2), compared with the unsharded service on one device in the same
process.

Timings print on earlier lines and are bring-up information, not
metrics. The last line is one JSON object naming the device; without a
TPU the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# the deployment (one chip) and its schedule
M, P, S, N, CHUNKS = 128, 1024, 16, 512, 6
REFIT_EVERY = 2 * N
CLIENTS, MIN_REQUESTS = 4, 256
REFIT_TOL = 1e-5

# Tolerances. STATS_TOL and SCORE_TOL bound one bf16 pass of the MXU
# over f32 operands (unit roundoff 2^-9 per operand, so 2^-8 per
# product), doubled: |dSigma_ij| <= STATS_TOL * sqrt(Sigma_ii Sigma_jj)
# and |dc_j| <= STATS_TOL * sqrt(Sigma_jj E[y^2]) by Cauchy-Schwarz on
# the mean of products, |d score| <= SCORE_TOL * sum_j |x_j beta_j|. A
# chunk dropped, doubled or mis-tiled moves entries by O(1) of those
# scales. BETA_TOL is 1/15 of the smallest true coefficient (0.3), so a
# model that passes cannot differ from the reference in which features
# it keeps; it is not tighter because the service's warm refits stop at
# REFIT_TOL while the reference runs its full cold budgets.
STATS_TOL = 2.0 ** -7
SCORE_TOL = 2.0 ** -7
BETA_TOL = 0.02


class SmokeFailure(RuntimeError):
    """A check of the smoke failed."""


def _log(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _penalties(m: int, p: int, refit_every: int):
    """lam and mu at the sqrt(log p / n) rate of the first refit's rows;
    Lam between the per-task noise of the debiased rows (about
    sqrt(1.67 / n) for AR(0.5)) and the smallest coefficient, 0.3, each
    scaled by the sqrt(m) of the row norm over tasks."""
    base = math.sqrt(math.log(p) / refit_every)
    return 4.0 * base, base, 0.2 * math.sqrt(m)


def _data_fns(m: int, p: int, s: int, n: int):
    import jax
    import jax.numpy as jnp

    from repro.core import ar_covariance, sample_coefficients

    @jax.jit
    def regime(key):
        chol = jnp.linalg.cholesky(ar_covariance(p, 0.5)
                                   + 1e-9 * jnp.eye(p))
        B, support = sample_coefficients(key, p, m, s, low=0.3, high=1.0)
        return chol, B, support

    @jax.jit
    def chunk(key, chol, B):
        k_x, k_e = jax.random.split(key)
        X = jax.random.normal(k_x, (m, n, p)) @ chol.T
        y = jnp.einsum("tnp,pt->tn", X, B) + jax.random.normal(k_e, (m, n))
        return X, y

    return regime, chunk


def _ref_fold():
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST

    @jax.jit
    def fold(S, c, yy, X, y):
        return (S + jnp.einsum("tni,tnj->tij", X, X, precision=hi),
                c + jnp.einsum("tni,tn->ti", X, y, precision=hi),
                yy + jnp.sum(y * y, axis=1))

    return fold


def _stats_error(Sigmas, cs, S_ref, c_ref, yy_ref) -> float:
    """Largest error of the statistics relative to the STATS_TOL
    scales (<= 1 passes)."""
    import jax.numpy as jnp
    d = jnp.sqrt(jnp.diagonal(S_ref, axis1=1, axis2=2))        # (m, p)
    s_err = jnp.abs(Sigmas - S_ref) / (d[:, :, None] * d[:, None, :])
    c_err = jnp.abs(cs - c_ref) / (d * jnp.sqrt(yy_ref)[:, None])
    return float(jnp.maximum(jnp.max(s_err), jnp.max(c_err))) / STATS_TOL


def _oracle_fit(Sigmas, cs, lam, mu, Lam, lasso_iters, debias_iters):
    """Cold DSML fit on (Sigmas, cs) with every kernel replaced by its
    jnp oracle, at the highest matmul precision."""
    import jax

    from repro.core.engine import (
        debias_batched, inverse_hessian_batched, power_iteration_batched,
        solve_lasso_eq2,
    )
    from repro.core.prox import support_from_rows
    with jax.default_matmul_precision("highest"):
        lam_max = power_iteration_batched(Sigmas)
        beta_hat = solve_lasso_eq2(Sigmas, cs, lam, iters=lasso_iters,
                                   lam_max=lam_max, use_kernel=False)
        Ms = inverse_hessian_batched(Sigmas, mu, iters=debias_iters,
                                     lam_max=lam_max, use_kernel=False)
        beta_u = debias_batched(Sigmas, cs, beta_hat, Ms)
        support = support_from_rows(beta_u.T, Lam)
        return beta_u * support[None, :], support


def _service(m, p, n, refit_every, mesh=None):
    from repro.stream import StreamingDsmlService
    lam, mu, Lam = _penalties(m, p, refit_every)
    return StreamingDsmlService(
        m, p, lam=lam, mu=mu, Lam=Lam, decay=1.0, refit_every=refit_every,
        max_refit_interval=refit_every, refit_tol=REFIT_TOL, chunk_n=n,
        mesh=mesh)


def _route_counts() -> dict:
    from repro import obs
    out = {}
    for c in obs.get_registry().snapshot()["counters"]:
        if c["name"] == "dispatch.route":
            lab = c["labels"]
            key = (lab["kernel"], lab["outcome"], lab["reason"],
                   lab["blocks"])
            out[key] = out.get(key, 0) + c["value"]
    return out


def _autotune_report(m, p, n) -> None:
    """Print whether this process swept or served defaults, and the
    tiles the engine will look up for the deployment's shapes."""
    from repro import obs
    from repro.kernels import autotune
    events = {e: int(obs.counter_total("autotune.cache", event=e))
              for e in ("miss_sweep", "hit_disk", "hit_memory")}
    if events["miss_sweep"]:
        how = f"swept {events['miss_sweep']} shapes on this chip"
    elif events["hit_disk"]:
        how = f"served {events['hit_disk']} winners from {autotune.cache_path()}"
    else:
        how = "no sweep off TPU: deterministic default tiles"
    _log(f"autotune: {how}")
    _log("autotune winners: fista r=1 "
         f"{autotune.autotune_block(m, p, 1, sweep=False)}, fista r=p "
         f"{autotune.autotune_block(m, p, p, sweep=False)}, rank_update "
         f"{autotune.autotune_rank_block(m, n, p, sweep=False)}, "
         f"logistic_grad "
         f"{autotune.autotune_logistic_block(m, n, p, sweep=False)}")


def _device_bytes() -> str:
    import jax
    parts = []
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        parts.append(f"{d.id}:{st.get('bytes_in_use', 'n/a')}"
                     f"/peak {st.get('peak_bytes_in_use', 'n/a')}")
    return ", ".join(parts)


def _serve_clients(front, p, seed, stop, results, errors):
    import numpy as np

    def client(i):
        rng = np.random.default_rng([seed, i])
        quota = MIN_REQUESTS // CLIENTS
        try:
            while len(results[i]) < quota or not stop.is_set():
                x = rng.standard_normal(p).astype(np.float32)
                results[i].append((x, front.predict(x, timeout=120)))
        except Exception as e:  # noqa: BLE001 - re-raised by the caller
            errors.append(e)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(CLIENTS)]
    for t in threads:
        t.start()
    return threads


def _check_served(results, snaps) -> int:
    import numpy as np
    count, worst = 0, 0.0
    for res in results:
        for x, r in res:
            _check(r.generation in snaps,
                   f"response names generation {r.generation}, never "
                   f"published during serving ({sorted(snaps)})")
            beta = snaps[r.generation]
            x64 = x.astype(np.float64)
            ref = beta @ x64
            bound = SCORE_TOL * (np.abs(beta) @ np.abs(x64)) + 1e-6
            err = np.abs(np.asarray(r.scores, np.float64)[:, 0] - ref)
            worst = max(worst, float(np.max(err / bound)))
            count += 1
    _check(worst <= 1.0, f"served scores off their generation's float64 "
                         f"product: {worst:.3g} x SCORE_TOL bound")
    _log(f"serve: {count} responses match their generation's float64 "
         f"product (worst {worst:.3g} of the bound)")
    return count


def run_smoke(*, m: int = M, p: int = P, s: int = S, n: int = N,
              chunks: int = CHUNKS, seed: int = 0) -> dict:
    """The one-chip run at the given sizes; raises SmokeFailure when a
    check fails. Returns a summary of what was checked."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import obs
    from repro.stream import ServingFront

    _check(obs.enabled(), "telemetry is off (REPRO_OBS=0): the route "
                          "check needs the dispatch.route counters")
    obs.reset()
    refit_every = 2 * n
    lam, mu, Lam = _penalties(m, p, refit_every)
    _log(f"deployment m={m} p={p} s={s}, {chunks} chunks x {n} rows, "
         f"refit every {refit_every} rows, lam={lam:.4g} mu={mu:.4g} "
         f"Lam={Lam:.4g}, seed {seed}")
    regime, chunk = _data_fns(m, p, s, n)
    fold = _ref_fold()

    # set-up and compile: a throwaway service on other data runs every
    # shape once (ingest, cold and warm refit, each predict bucket)
    t0 = time.perf_counter()
    k_warm, k_regime, k_data = jax.random.split(jax.random.PRNGKey(seed), 3)
    chol, B, support = jax.block_until_ready(regime(k_regime))
    warm = _service(m, p, n, refit_every)
    _autotune_report(m, p, n)
    for k in jax.random.split(k_warm, 4):
        X, y = chunk(k, chol, B)
        warm.ingest(X, y)
    for rows in (8, 16, 32, 64):
        warm.predict(jnp.zeros((rows, p), jnp.float32))
    jax.block_until_ready(warm.state)
    _check(warm.generation == 2, f"warm-up adopted {warm.generation} refits")
    S_acc = jnp.zeros((m, p, p), jnp.float32)
    c_acc = jnp.zeros((m, p), jnp.float32)
    yy_acc = jnp.zeros((m,), jnp.float32)
    jax.block_until_ready(fold(S_acc, c_acc, yy_acc, X, y))
    del warm, X, y
    _log(f"time set-up and compile: {time.perf_counter() - t0:.3f} s")

    svc = _service(m, p, n, refit_every)
    front = ServingFront(svc, max_batch=64, max_delay_ms=2.0)
    stop, errors = threading.Event(), []
    results = [[] for _ in range(CLIENTS)]
    snaps, threads = {}, []

    def snapshot():
        snap = svc.serving()
        snaps.setdefault(snap.generation,
                         np.asarray(snap.beta_tilde, np.float64))

    serve_from = chunks - 2
    for i, k in enumerate(jax.random.split(k_data, chunks)):
        X, y = chunk(k, chol, B)
        S_acc, c_acc, yy_acc = fold(S_acc, c_acc, yy_acc, X, y)
        jax.block_until_ready((X, y, S_acc))
        if i == serve_from:
            snapshot()
            front.start()
            threads = _serve_clients(front, p, seed, stop, results, errors)
        t1 = time.perf_counter()
        info = svc.ingest(X, y)
        jax.block_until_ready(svc.state)
        dt = time.perf_counter() - t1
        what = "ingest"
        if info is not None:
            what = (f"ingest + refit -> generation {int(info.generation)}, "
                    f"|S|={int(info.support_size)}, lasso/debias iters "
                    f"{int(info.lasso_iters_run)}/"
                    f"{int(info.debias_iters_run)}")
        _log(f"time chunk {i + 1}/{chunks} {what}: {dt:.3f} s"
             + (" (serving)" if i >= serve_from else ""))
        if i >= serve_from:
            snapshot()
    stop.set()
    for t in threads:
        t.join(timeout=300)
    front.stop()
    _check(not errors, f"a serving client failed: {errors[:1]!r}")
    _check(not any(t.is_alive() for t in threads), "a client hung")

    _check(svc.generation == chunks * n // refit_every and svc.rollbacks == 0,
           f"expected {chunks * n // refit_every} adopted refits, got "
           f"generation {svc.generation} with {svc.rollbacks} rollbacks")
    _log(f"refits adopted: {svc.generation}, rollbacks: {svc.rollbacks}")

    rows = chunks * n
    S_ref, c_ref, yy_ref = S_acc / rows, c_acc / rows, yy_acc / rows
    st = svc.state
    stats_err = _stats_error(st.Sigmas, st.cs, S_ref, c_ref, yy_ref)
    _check(stats_err <= 1.0, f"statistics off the HIGHEST-precision "
                             f"einsum: {stats_err:.3g} x STATS_TOL scale")
    _log(f"statistics vs einsum(HIGHEST) over {rows} rows/task: worst "
         f"{stats_err * STATS_TOL:.3g} of sqrt(Sigma_ii Sigma_jj) "
         f"(tolerance {STATS_TOL:.3g})")

    sup = np.asarray(st.support)
    truth = np.asarray(support)
    norms = np.linalg.norm(np.asarray(st.beta_u), axis=0)
    _check(np.array_equal(sup, truth),
           f"support != ground truth: {int(np.sum(sup & ~truth))} false, "
           f"{int(np.sum(truth & ~sup))} missed")
    _log(f"support == ground truth ({int(truth.sum())} features); row "
         f"norms on support >= {norms[truth].min():.4g}, off support <= "
         f"{norms[~truth].max():.4g}, Lam {Lam:.4g}")

    t1 = time.perf_counter()
    ref_beta, ref_sup = _oracle_fit(st.Sigmas, st.cs, lam, mu, Lam,
                                    svc.lasso_iters, svc.debias_iters)
    jax.block_until_ready(ref_beta)
    _log(f"time oracle reference fit: {time.perf_counter() - t1:.3f} s")
    _check(np.array_equal(np.asarray(ref_sup), sup),
           "support != the oracle reference fit's support")
    beta_err = float(jnp.max(jnp.abs(st.beta_tilde - ref_beta)))
    _check(beta_err <= BETA_TOL, f"beta_tilde off the oracle reference "
                                 f"by {beta_err:.3g} > {BETA_TOL}")
    _log(f"oracle reference: same support, max|beta_tilde - ref| = "
         f"{beta_err:.3g} (tolerance {BETA_TOL})")

    served = _check_served(results, snaps)
    _check(served >= MIN_REQUESTS, f"only {served} requests served")
    lat = obs.hist_quantiles("serve.request_ms", (0.5, 0.99)) or {}
    _log(f"serve: generations {sorted(snaps)}, request ms p50 "
         f"{lat.get(0.5, float('nan')):.3f} p99 "
         f"{lat.get(0.99, float('nan')):.3f} (bring-up timing)")

    routes = _route_counts()
    for (kernel, outcome, reason, blocks), v in sorted(routes.items()):
        _log(f"route {kernel} {outcome} reason={reason} blocks={blocks}: "
             f"{int(v)}")
    main_path = ("rank_update", "fista_step_batched", "ista_step_batched")
    fallbacks = [k for k in routes if k[0] in main_path and k[1] == "oracle"]
    _check(not fallbacks, f"main-path kernels fell back to the oracle: "
                          f"{fallbacks}")
    for kernel in ("rank_update", "fista_step_batched"):
        _check(any(k[0] == kernel and k[1] == "kernel" for k in routes),
               f"{kernel} never took the kernel path")
    _log(f"device bytes in use: {_device_bytes()}")
    return {"refits": svc.generation, "served": served,
            "stats_err": stats_err, "beta_err": beta_err,
            "support": int(sup.sum())}


def run_sharded(*, m: int = M, p: int = P, s: int = S, n: int = N,
                chunks: int = CHUNKS, seed: int = 0) -> dict:
    """The sharded service over a data=2 x task=2 mesh against the
    unsharded service on one device, same data and schedule."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.substrate import data_task_mesh

    _check(jax.device_count() >= 4, f"needs 4 devices, found "
                                    f"{jax.device_count()}")
    refit_every = 2 * n
    mesh = data_task_mesh(n_task=2)
    _log(f"sharded deployment m={m} p={p} s={s}, {chunks} chunks x {n} "
         f"rows on mesh {dict(mesh.shape)}; chunk times include the "
         f"compilation of each new shape")
    regime, chunk = _data_fns(m, p, s, n)
    k_regime, k_data = jax.random.split(jax.random.PRNGKey(seed))
    chol, B, support = regime(k_regime)
    t0 = time.perf_counter()
    sharded = _service(m, p, n, refit_every, mesh=mesh)
    single = _service(m, p, n, refit_every)
    _log(f"time set-up: {time.perf_counter() - t0:.3f} s")
    yy = jnp.zeros((m,), jnp.float32)
    for i, k in enumerate(jax.random.split(k_data, chunks)):
        X, y = chunk(k, chol, B)
        yy = yy + jnp.sum(y * y, axis=1) / (chunks * n)
        for name, svc in (("sharded", sharded), ("one device", single)):
            t1 = time.perf_counter()
            info = svc.ingest(X, y)
            jax.block_until_ready(svc.state)
            refit = "" if info is None else \
                f" + refit -> generation {int(info.generation)}"
            _log(f"time chunk {i + 1}/{chunks} {name} ingest{refit}: "
                 f"{time.perf_counter() - t1:.3f} s")
    want = chunks * n // refit_every
    for name, svc in (("sharded", sharded), ("one device", single)):
        _check(svc.generation == want and svc.rollbacks == 0,
               f"{name}: generation {svc.generation}, {svc.rollbacks} "
               f"rollbacks (expected {want} adopted refits)")
    a, b = sharded.state, single.state
    _log(f"Sigmas sharding: {a.Sigmas.sharding}")
    for shard in a.Sigmas.addressable_shards:
        _log(f"Sigmas shard on device {shard.device.id}: index "
             f"{shard.index}, {shard.data.nbytes} bytes")
    _log(f"device bytes in use: {_device_bytes()}")
    a_S, a_c = jax.device_put((a.Sigmas, a.cs), jax.devices()[0])
    stats_err = _stats_error(a_S, a_c, b.Sigmas, b.cs, yy)
    _check(stats_err <= 1.0, f"sharded statistics off the one-device "
                             f"service: {stats_err:.3g} x STATS_TOL scale")
    _log(f"statistics sharded vs one device: worst "
         f"{stats_err * STATS_TOL:.3g} of the scale (tolerance "
         f"{STATS_TOL:.3g})")
    sup_a, sup_b = np.asarray(a.support), np.asarray(b.support)
    _check(np.array_equal(sup_a, sup_b), "sharded support != one-device")
    _check(np.array_equal(sup_b, np.asarray(support)),
           "support != ground truth")
    beta_err = float(jnp.max(jnp.abs(
        jax.device_put(a.beta_tilde, jax.devices()[0]) - b.beta_tilde)))
    _check(beta_err <= BETA_TOL, f"sharded beta_tilde off the one-device "
                                 f"service by {beta_err:.3g}")
    _log(f"sharded vs one device: same support (== ground truth, "
         f"{int(sup_a.sum())} features), max|d beta_tilde| = "
         f"{beta_err:.3g} (tolerance {BETA_TOL})")
    return {"stats_err": stats_err, "beta_err": beta_err}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded path and its "
                         "one-device comparison")
    args = ap.parse_args(argv)

    from repro.substrate import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}",
              file=sys.stderr)
        return 2
    _log(f"device {dev.device_kind} x {len(devices)}, jax "
         f"{jax.__version__}, compile cache {cache}")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_sharded(seed=args.seed)
        else:
            run_smoke(seed=args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    st = dev.memory_stats() or {}
    _log(f"peak bytes in use on device {dev.id}: "
         f"{st.get('peak_bytes_in_use', 'n/a')}")
    _log(f"time total: {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
