"""Serve predictions while the model keeps learning: a background
thread folds a drifting stream into `StreamingDsmlService` (refits
adopt new model generations by atomic snapshot swap) while a
`ServingFront` microbatches predict requests from a pool of
closed-loop client threads.

    PYTHONPATH=src python examples/serve_front.py [--smoke] [--clients 4]

Watch for: client latency stays flat through refits (readers hold
immutable `ModelGeneration` snapshots — adoption never blocks or
tears a predict), every response carries the generation that served
it, and the generation counter climbs while traffic flows.
"""
import argparse
import threading
import time

import numpy as np

from repro import obs
from repro.stream import ServingFront, StreamingDsmlService


def make_stream(rng, m, p, s, n_chunk, chunks):
    """A drifting regression stream: the true coefficients take a
    random walk, so the drift-aware service keeps refitting."""
    B = np.zeros((m, p), np.float32)
    B[:, rng.choice(p, s, replace=False)] = rng.standard_normal((m, s))
    for _ in range(chunks):
        B += 0.02 * rng.standard_normal(B.shape).astype(np.float32)
        X = rng.standard_normal((m, n_chunk, p)).astype(np.float32)
        y = (np.einsum("tnp,tp->tn", X, B)
             + 0.1 * rng.standard_normal((m, n_chunk))).astype(np.float32)
        yield X, y


def main(argv=None):
    """Run the demo; returns the headline metrics dict (request count,
    latency quantiles, generations served) for smoke assertions."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--m", type=int, default=8)
    ap.add_argument("--p", type=int, default=128)
    ap.add_argument("--s", type=int, default=8)
    ap.add_argument("--chunk-size", type=int, default=256)
    ap.add_argument("--chunks", type=int, default=24)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--smoke", action="store_true", help="tiny CI sizes")
    args = ap.parse_args(argv)
    if args.smoke:
        args.m, args.p, args.s = 4, 48, 5
        args.chunk_size, args.chunks = 64, 8

    rng = np.random.default_rng(0)
    svc = StreamingDsmlService(
        args.m, args.p, lam=0.4, mu=0.2, Lam=1.0, decay=0.9,
        refit_every=args.chunk_size, max_refit_interval=2 * args.chunk_size,
        lasso_iters=200, debias_iters=300, refit_tol=1e-5)
    stream = make_stream(rng, args.m, args.p, args.s,
                         args.chunk_size, args.chunks)
    svc.ingest(*next(stream))           # first model + jit warmup

    def feeder():
        for X, y in stream:
            svc.ingest(X, y)

    stop = threading.Event()
    gens_seen = set()
    latencies = []
    lock = threading.Lock()

    def client():
        q = rng.standard_normal(args.p).astype(np.float32)
        while not stop.is_set():
            t0 = time.perf_counter()
            res = front.predict(q, timeout=30)
            dt = (time.perf_counter() - t0) * 1e3
            with lock:
                gens_seen.add(res.generation)
                latencies.append(dt)

    with ServingFront(svc, max_batch=64, max_delay_ms=2.0) as front:
        front.predict(np.zeros(args.p, np.float32))   # compile first
        feed = threading.Thread(target=feeder)
        pool = [threading.Thread(target=client)
                for _ in range(args.clients)]
        feed.start()
        for c in pool:
            c.start()
        feed.join()                     # serve until the stream runs dry
        stop.set()
        for c in pool:
            c.join()
        q = front.latency_quantiles() or {}   # None under REPRO_OBS=0
        p50, p99 = q.get(0.5, 0.0), q.get(0.99, 0.0)

    metrics = {
        "requests": len(latencies),
        "client_p50_ms": float(np.percentile(latencies, 50)),
        "client_p99_ms": float(np.percentile(latencies, 99)),
        "front_p50_ms": p50,
        "front_p99_ms": p99,
        "generations_served": len(gens_seen),
        "final_generation": svc.generation,
        "batches": obs.counter_total("serve.batches"),
    }
    print(f"served {metrics['requests']} requests over "
          f"{metrics['generations_served']} model generations "
          f"(final gen {metrics['final_generation']})")
    print(f"client latency p50={metrics['client_p50_ms']:.2f}ms "
          f"p99={metrics['client_p99_ms']:.2f}ms; front-side "
          f"p50={p50:.2f}ms p99={p99:.2f}ms over "
          f"{metrics['batches']:.0f} microbatches")
    return metrics


if __name__ == "__main__":
    from repro.substrate import enable_compile_cache
    enable_compile_cache()
    main()
