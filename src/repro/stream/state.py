"""Streaming sufficient-statistics state (checkpointable pytree).

The DSML estimator never touches raw samples after the reduction to
`(Sigma, c)`, and those statistics are *additive over samples*. A
stream of minibatches therefore folds into a fixed-size `StreamState`
— per-task running covariance/correlation means plus an effective
sample count — and the full pipeline (lasso, debias, threshold) can be
re-run at any time from the state alone. Three ingestion regimes:

  * plain (`decay=1`):   exact running means; ingesting a dataset in
                          any chunking reproduces `sufficient_stats`
                          on the concatenation (to float roundoff).
  * exponential decay:    `decay<1` multiplies the *old* effective
                          count per ingested chunk, so a chunk that is
                          j chunks old carries weight decay^j — cheap
                          forgetting for non-stationary traffic.
  * sliding window:       `WindowState` keeps the last w chunk stats
                          in a ring buffer; `window_stats` aggregates
                          exactly the surviving chunks.

All functions are pure and jit-safe; `StreamState` round-trips through
`checkpoint/io.save_pytree` unchanged.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.engine import sufficient_stats


class StreamState(NamedTuple):
    Sigmas: jnp.ndarray      # (m, p, p) running weighted-mean covariance
    cs: jnp.ndarray          # (m, p)    running weighted-mean correlation
    counts: jnp.ndarray      # (m,)      effective sample count (decays)
    beta_local: jnp.ndarray  # (m, p)    last step-1 lasso (refit warm start)
    Ms: jnp.ndarray          # (m, p, p) last debias M (refit warm start)
    beta_u: jnp.ndarray      # (m, p)    last debiased estimates
    beta_tilde: jnp.ndarray  # (m, p)    current servable estimates
    support: jnp.ndarray     # (p,) bool current shared support
    generation: jnp.ndarray  # ()   int32 refit generation


def state_shardings(mesh, task_axis: str = "task") -> StreamState:
    """Where a task-sharded state lives on `mesh`: every per-task field
    split over `task_axis`, the shared support and the generation
    replicated (and everything replicated over the other axes)."""
    T, R = NamedSharding(mesh, P(task_axis)), NamedSharding(mesh, P())
    return StreamState(Sigmas=T, cs=T, counts=T, beta_local=T, Ms=T,
                       beta_u=T, beta_tilde=T, support=R, generation=R)


def init_stream_state(m: int, p: int, dtype=jnp.float32, *, mesh=None,
                      task_axis: str = "task") -> StreamState:
    """Empty state for m tasks in p dimensions (zero samples seen).

    With a `mesh` the state is born task-sharded (`state_shardings`):
    each device builds only its own tasks' stacks, so no device ever
    holds more than its share of the (m, p, p) stacks."""
    if mesh is not None:
        return jax.jit(partial(init_stream_state, m, p, dtype),
                       out_shardings=state_shardings(mesh, task_axis))()
    return StreamState(
        Sigmas=jnp.zeros((m, p, p), dtype),
        cs=jnp.zeros((m, p), dtype),
        counts=jnp.zeros((m,), dtype),
        beta_local=jnp.zeros((m, p), dtype),
        Ms=jnp.zeros((m, p, p), dtype),
        beta_u=jnp.zeros((m, p), dtype),
        beta_tilde=jnp.zeros((m, p), dtype),
        support=jnp.zeros((p,), bool),
        generation=jnp.zeros((), jnp.int32),
    )


@jax.jit
def ingest_stats(state: StreamState, Sigma_b: jnp.ndarray, c_b: jnp.ndarray,
                 count_b: jnp.ndarray, decay=1.0) -> StreamState:
    """Fold one chunk's *mean* statistics into the running means.

    Sigma_b (m, p, p) and c_b (m, p) are chunk means weighted by
    `count_b` (scalar or (m,) effective samples). `decay` scales the
    old effective count first, so with decay d and chunk counts n_k the
    state equals  sum_k d^{K-k} n_k stats_k / sum_k d^{K-k} n_k.
    """
    dt = state.Sigmas.dtype
    count_b = jnp.broadcast_to(jnp.asarray(count_b, dt).reshape(-1),
                               state.counts.shape)
    w_old = jnp.asarray(decay, dt) * state.counts
    total = w_old + count_b
    denom = jnp.maximum(total, jnp.finfo(dt).tiny)
    Sigmas = (w_old[:, None, None] * state.Sigmas
              + count_b[:, None, None] * Sigma_b) / denom[:, None, None]
    cs = (w_old[:, None] * state.cs + count_b[:, None] * c_b) / denom[:, None]
    return state._replace(Sigmas=Sigmas, cs=cs, counts=total)


@jax.jit
def ingest(state: StreamState, X_batch: jnp.ndarray, y_batch: jnp.ndarray,
           weights: jnp.ndarray | None = None, decay=1.0) -> StreamState:
    """Rank-n update from a raw minibatch. X (m, n, p), y (m, n).

    The chunk reduction is `sufficient_stats`, i.e. on TPU the fused
    Pallas `kernels/rank_update` kernel — Sigma_b and c_b from ONE
    pass over the chunk (DESIGN.md §11) — and the XLA einsum oracle on
    CPU.

    `weights` (m, n) importance-weights samples within the chunk (the
    chunk's effective count becomes sum(weights) per task); `decay`
    applies exponential forgetting to everything already ingested.
    """
    n = X_batch.shape[1]
    Sigma_b, c_b = sufficient_stats(X_batch, y_batch, weights)
    if weights is None:
        count_b = jnp.full(state.counts.shape, n, state.counts.dtype)
    else:
        count_b = jnp.sum(weights, axis=1).astype(state.counts.dtype)
        # sufficient_stats normalizes by n, not sum(w): rescale the chunk
        # means so count_b * mean recovers the weighted sums.
        scale = n / jnp.maximum(count_b, jnp.finfo(state.counts.dtype).tiny)
        Sigma_b = Sigma_b * scale[:, None, None]
        c_b = c_b * scale[:, None]
    return ingest_stats(state, Sigma_b, c_b, count_b, decay)


@jax.jit
def merge(a: StreamState, b: StreamState) -> StreamState:
    """Additive merge of two states' statistics (shards of one stream).

    Model fields (beta/support/generation) follow `a`; only the
    sufficient statistics and counts combine.
    """
    return ingest_stats(a, b.Sigmas, b.cs, b.counts)


# ---------------------------------------------------------------------------
# sliding window
# ---------------------------------------------------------------------------

class WindowState(NamedTuple):
    Sigmas: jnp.ndarray   # (w, m, p, p) per-slot chunk mean covariance
    cs: jnp.ndarray       # (w, m, p)    per-slot chunk mean correlation
    counts: jnp.ndarray   # (w, m)       per-slot sample counts (0 = empty)
    head: jnp.ndarray     # ()  int32    next slot to overwrite
    seen: jnp.ndarray     # ()  int32    total chunks ever ingested


def init_window(window: int, m: int, p: int, dtype=jnp.float32) -> WindowState:
    return WindowState(
        Sigmas=jnp.zeros((window, m, p, p), dtype),
        cs=jnp.zeros((window, m, p), dtype),
        counts=jnp.zeros((window, m), dtype),
        head=jnp.zeros((), jnp.int32),
        seen=jnp.zeros((), jnp.int32),
    )


@jax.jit
def window_ingest(win: WindowState, X_batch: jnp.ndarray,
                  y_batch: jnp.ndarray) -> WindowState:
    """Write one chunk's stats into the ring buffer (evicts the oldest)."""
    n = X_batch.shape[1]
    Sigma_b, c_b = sufficient_stats(X_batch, y_batch)
    w = win.counts.shape[0]
    h = win.head
    return WindowState(
        Sigmas=win.Sigmas.at[h].set(Sigma_b.astype(win.Sigmas.dtype)),
        cs=win.cs.at[h].set(c_b.astype(win.cs.dtype)),
        counts=win.counts.at[h].set(
            jnp.full(win.counts.shape[1:], n, win.counts.dtype)),
        head=(h + 1) % w,
        seen=win.seen + 1,
    )


@jax.jit
def window_stats(win: WindowState
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Aggregate the surviving chunks: (Sigmas (m,p,p), cs (m,p), counts (m,)).

    Equals `sufficient_stats` on the concatenation of the last
    min(seen, window) chunks.
    """
    total = jnp.sum(win.counts, axis=0)                       # (m,)
    denom = jnp.maximum(total, jnp.finfo(win.counts.dtype).tiny)
    Sigmas = jnp.einsum("wm,wmij->mij", win.counts, win.Sigmas) \
        / denom[:, None, None]
    cs = jnp.einsum("wm,wmi->mi", win.counts, win.cs) / denom[:, None]
    return Sigmas, cs, total
