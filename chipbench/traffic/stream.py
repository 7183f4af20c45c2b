"""The load generator of every cell: a closed-loop chunk stream into
`StreamingDsmlService.ingest` with open-loop predicts through
`ServingFront.submit`.

A traffic file (`traffic/<name>.json`) names this driver and gives its
parameters:

* `pool_chunks`: distinct (m, n, p) chunks made on the device from the
  seed during set-up and then held in host memory, because clients hand
  the service host data. The stream cycles through them in an order
  drawn from the seed.
* `refit_every_rows`, `max_refit_interval_rows`: the service's cadence,
  in rows per task.
* `setup_chunks`: set-up ingests this many chunks, enough for the
  cadence to run the cold and the warm refit, so that both have
  compiled and run before the window. The window closes at the first
  publish of a new generation after `--seconds`.
* `predict_rate_per_s`, `predict_rows_pool`: Poisson arrivals of one-row
  predict requests at a fixed rate, rows drawn from the same design.
  Every seed gets the same set of inter-arrival gaps in every second, in
  another order.

The chunk stream runs on the calling thread, which is the service's one
driver thread; predicts come from a generator thread on their own
schedule, each timed from the moment it was due until its result
arrived.
"""
from __future__ import annotations

import math
import threading
import time

import numpy as np

GAP_HORIZON_S = 120.0          # arrivals drawn beyond the longest window
GAP_BLOCK_S = 1.0              # span of one block of arrival gaps
DRAIN_S = 60.0                 # wait for requests due in the window
OVERRUN_S = 30.0               # a window that sees no boundary ends here


class StreamRun:
    """One run of one cell: `setup()`, `window(seconds)`, `outputs()`."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, log):
        self.cfg, self.tp, self.seed, self.log = cfg, traffic, seed, log
        self.rng = np.random.default_rng(seed)
        self.m, self.p, self.n = cfg["m"], cfg["p"], cfg["chunk_n"]

    # -- set-up -----------------------------------------------------------

    def make_pool(self):
        """Chunks and predict rows on the device from the seed, then to
        host memory."""
        import jax

        from chipbench import reference
        d = self.cfg["design"]
        key = jax.random.PRNGKey(int(self.rng.integers(0, 2 ** 31 - 1)))
        k_b, k_rows, k_chunks = jax.random.split(key, 3)
        chol = reference.ar_cholesky(self.p, d["rho"])
        B, _ = reference.coefficients(k_b, m=self.m, p=self.p, s=self.cfg["s"],
                                      low=d["coef_low"], high=d["coef_high"])
        P = self.tp["pool_chunks"]
        X = np.empty((P, self.m, self.n, self.p), np.float32)
        y = np.empty((P, self.m, self.n), np.float32)
        for k in range(P):
            Xk, yk = reference.chunk(jax.random.fold_in(k_chunks, k), chol, B,
                                     m=self.m, n=self.n, noise=d["noise_sd"])
            X[k], y[k] = np.asarray(Xk), np.asarray(yk)
            del Xk, yk
        rows = np.asarray(reference.design_rows(
            k_rows, chol, rows=self.tp["predict_rows_pool"]), np.float32)
        self.pool = (X, y, rows)
        self.order = self.rng.permutation(P)
        self.row_order = self.rng.permutation(len(rows))

    def _next_chunk(self):
        k = int(self.order[self.offered % len(self.order)])
        self.offered += 1
        return k

    def _ingest(self, k):
        """Offer pool chunk k; record whether it folded and what it
        published. Returns the RefitInfo or None."""
        import jax

        svc = self.svc
        quarantined = svc.guard.total_quarantined
        with jax.profiler.TraceAnnotation("bench.ingest"):
            info = svc.ingest(self.pool[0][k], self.pool[1][k])
        if svc.guard.total_quarantined != quarantined:
            self.unfolded += 1
            return None
        self.sequence.append(k)
        snap = svc.serving()
        self.published.setdefault(snap.generation, snap.beta_tilde)
        return info

    def setup(self):
        import jax
        import jax.numpy as jnp

        from chipbench.check import penalties
        from repro.stream import ServingFront, StreamingDsmlService
        from repro.stream.serve import bucket_rows
        s, tp = self.cfg["service"], self.tp
        self.make_pool()
        lam, mu, Lam = penalties(self.cfg)
        self.svc = StreamingDsmlService(
            self.m, self.p, lam=lam, mu=mu, Lam=Lam, decay=s["decay"],
            refit_every=tp["refit_every_rows"],
            max_refit_interval=tp["max_refit_interval_rows"],
            lasso_iters=s["lasso_iters"], debias_iters=s["debias_iters"],
            warm_lasso_iters=s["warm_lasso_iters"],
            warm_debias_iters=s["warm_debias_iters"],
            refit_tol=s["refit_tol"], chunk_n=self.n, guard=s["guard"])
        refit = self.svc.refit

        def annotated_refit():
            # the service calls self.refit() from ingest: this puts the
            # refit call on the trace's host timeline
            with jax.profiler.TraceAnnotation("bench.refit"):
                return refit()
        self.svc.refit = annotated_refit
        f = self.cfg["front"]
        self.front = ServingFront(self.svc, max_batch=f["max_batch"],
                                  max_delay_ms=f["max_delay_ms"])
        for b in sorted({bucket_rows(r) for r in range(1, f["max_batch"] + 1)}):
            np.asarray(self.svc.predict(jnp.zeros((b, self.p), jnp.float32)))
        self.offered, self.unfolded, self.sequence = 0, 0, []
        self.published = {}
        for i in range(tp["setup_chunks"]):
            self._ingest(self._next_chunk())
        jax.block_until_ready(self.svc.state)
        if self.svc.generation < 2:
            raise RuntimeError(f"set-up ran {self.svc.generation} refits; the "
                               f"cold and the warm refit must both run")
        self.front.start()

    # -- the window -------------------------------------------------------

    def _arrivals(self):
        """Due times (s from the window's start). Each block of
        GAP_BLOCK_S holds the same set of exponential gaps, the quantiles
        of a Poisson process at the rate, in an order drawn from the
        seed: every seed offers the same number of requests in every
        block, and only their order within it differs."""
        rate = self.tp["predict_rate_per_s"]
        per = max(1, int(round(rate * GAP_BLOCK_S)))
        gaps = -np.log1p(-(np.arange(per) + 0.5) / per) / rate
        blocks = int(math.ceil(GAP_HORIZON_S / gaps.sum()))
        return np.cumsum(np.concatenate(
            [self.rng.permutation(gaps) for _ in range(blocks)]))

    def _generate(self, t0, due, done, submitted, futures):
        import jax
        rows, order, front = self.pool[2], self.row_order, self.front
        for i in range(len(due)):
            at = t0 + due[i]
            if at >= self._close:
                break
            wait = at - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
                if at >= self._close:
                    break
            with jax.profiler.TraceAnnotation("bench.predict"):
                fut = front.submit(rows[order[i % len(order)]])
            submitted[i] = time.perf_counter()
            fut.add_done_callback(
                lambda _f, i=i: done.__setitem__(i, time.perf_counter()))
            futures.append(fut)
        else:
            self.arrivals_exhausted = True

    def window(self, seconds: float) -> dict:
        """Run the window; returns what the end-to-end metrics read."""
        import jax
        due = self._arrivals()
        done = np.full(len(due), np.nan)
        submitted = np.full(len(due), np.nan)
        futures = []
        self._close, self.arrivals_exhausted = math.inf, False
        chunks0, unfolded0 = len(self.sequence), self.unfolded
        publishes, timeline = 0, []
        t0 = time.perf_counter()
        gen = threading.Thread(target=self._generate, name="bench-predicts",
                               args=(t0, due, done, submitted, futures))
        gen.start()
        with jax.profiler.TraceAnnotation("bench.window"):
            while True:
                before = self.svc.serving().generation
                start = time.perf_counter() - t0
                self._ingest(self._next_chunk())
                now = self.svc.serving().generation
                publishes += now != before
                elapsed = time.perf_counter() - t0
                timeline.append((start, elapsed, bool(now != before)))
                if elapsed >= seconds + OVERRUN_S:
                    self.log("WARNING: no cycle boundary in the window's "
                             f"last {OVERRUN_S} s")
                    break
                if now != before and elapsed >= seconds:
                    break
            t1 = time.perf_counter()
        self._close = t1
        gen.join(DRAIN_S)
        n_req = len(futures)
        deadline = t1 + DRAIN_S
        for fut in futures:
            try:
                fut.exception(timeout=max(0.0, deadline - time.perf_counter()))
            except TimeoutError:
                pass
        self.front.stop()
        self.futures = futures
        chunks = len(self.sequence) - chunks0
        lat = (done[:n_req] - (t0 + due[:n_req])) * 1e3
        ok = np.array([f.done() and f.exception() is None for f in futures],
                      bool)
        return {
            "window_s": t1 - t0, "chunks": chunks,
            "rows_folded": chunks * self.m * self.n,
            "chunks_unfolded": self.unfolded - unfolded0,
            "requests": n_req, "requests_failed": int(n_req - ok.sum()),
            "latency_ms": lat[ok],
            "lateness_ms": (submitted[:n_req] - (t0 + due[:n_req])) * 1e3,
            "due_ms": due[:n_req][ok] * 1e3, "publishes": publishes,
            "generator_alive": gen.is_alive(),
            "arrivals_exhausted": self.arrivals_exhausted,
            "timeline": timeline,
        }

    # -- what the check compares ------------------------------------------

    def outputs(self, sample: int) -> dict:
        """Host copies of what the timed path produced: the statistics and
        the model of the generation that closed the window (the last
        refit, on the final statistics), and `sample` served responses
        drawn from the seed. Drops the service, so that its device memory
        is free for the reference."""
        st = self.svc.state
        done = [i for i, f in enumerate(self.futures)
                if f.done() and f.exception() is None]
        pick = sorted(self.rng.choice(len(done), min(sample, len(done)),
                                      replace=False)) if done else []
        order = self.row_order
        rows, gens, scores = [], [], []
        for j in pick:
            res = self.futures[done[j]].result()
            rows.append(int(order[done[j] % len(order)]))
            gens.append(int(res.generation))
            scores.append(np.asarray(res.scores, np.float32)[:, 0])
        out = {
            "sequence": list(self.sequence),
            "generation": int(st.generation),
            **{k: np.asarray(getattr(st, k)) for k in (
                "Sigmas", "cs", "beta_local", "Ms", "beta_u", "beta_tilde",
                "support")},
            "published": {g: np.asarray(b) for g, b in self.published.items()},
            "served_rows": np.asarray(rows, np.int64),
            "served_generations": np.asarray(gens, np.int64),
            "served_scores": np.asarray(scores, np.float32).reshape(
                len(scores), self.m),
            "responses": len(done),
        }
        del st
        self.svc = self.front = self.published = self.futures = None
        return out
