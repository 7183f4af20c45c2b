"""Incremental DSML refresh from streaming sufficient statistics.

A refit re-runs Algorithm 1's compute (local lasso -> debias ->
group-threshold) on the state's current `(Sigma, c)` — identical math
to `dsml_fit` on the data the state has absorbed, but with the step-1
FISTA warm-started from the previous solution. Warm starts matter
because consecutive refits see nearly identical statistics: the
iterates start at (numerically) the previous optimum, so a fraction of
the cold iteration budget reaches the same tolerance — that is the
warm/cold gap `benchmarks/stream_bench.py` measures.

`RefitInfo.jaccard` reports support drift against the previous
generation so callers can refit lazily: an unchanged support (jaccard
== 1) means the served model has not moved and the next refit can wait.

On a mesh the two solves run per task shard inside `shard_map`: their
FISTA steps are Pallas kernels on TPU, which the SPMD partitioner
cannot split, and the tasks are independent, so each device solves its
own tasks' stacks and nothing is gathered.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.engine import (
    debias_batched, inverse_hessian_batched, power_iteration_batched,
    scaled_identity_m0, solve_lasso_eq2, solve_logistic_lasso_batched,
)
from repro.core.logistic import debias_logistic_batched
from repro.core.prox import support_from_rows
from repro.stream.state import StreamState, state_shardings
from repro.substrate import shard_map


class RefitInfo(NamedTuple):
    jaccard: jnp.ndarray        # () similarity of new vs previous support
    support_size: jnp.ndarray   # () int32 |S_hat| after thresholding
    generation: jnp.ndarray     # () int32 generation of the NEW state
    # iterations the two solves actually ran (== the ceilings unless a
    # tol was set); None on paths that never count (e.g. rollback infos).
    # On a mesh these are the largest over the task shards, and the
    # shard_* fields hold each shard's count (None on one device)
    lasso_iters_run: jnp.ndarray | None = None
    debias_iters_run: jnp.ndarray | None = None
    shard_lasso_iters: jnp.ndarray | None = None
    shard_debias_iters: jnp.ndarray | None = None


def jaccard_support(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """|a & b| / |a | b|, defined as 1.0 when both supports are empty."""
    inter = jnp.sum(a & b)
    union = jnp.sum(a | b)
    return jnp.where(union > 0, inter / jnp.maximum(union, 1), 1.0)


def _solves(Sigmas, cs, lam, mu, beta0, M0, tol, *, lasso_iters: int,
            debias_iters: int):
    """Steps 1-2 of Algorithm 1 for a batch of tasks: the lasso and the
    debias M solve, sharing one power iteration. Returns (beta_hat, Ms,
    lasso iterations run, debias iterations run)."""
    with jax.named_scope("refit.power"):
        lam_max = power_iteration_batched(Sigmas)
    with jax.named_scope("refit.lasso"):
        beta_hat, lasso_run = solve_lasso_eq2(
            Sigmas, cs, lam, iters=lasso_iters, beta0=beta0,
            lam_max=lam_max, tol=tol, return_iters=True)
    with jax.named_scope("refit.msolve"):
        Ms, debias_run = inverse_hessian_batched(
            Sigmas, mu, iters=debias_iters, M0=M0, lam_max=lam_max,
            tol=tol, return_iters=True)
    return beta_hat, Ms, lasso_run, debias_run


def _sharded_solves(mesh, task_axis, Sigmas, cs, lam, mu, beta0, M0, tol,
                    **iters):
    """`_solves` per task shard of `mesh`. A cold refit's starts are
    spelled out (zeros; the engine's scaled identity, which is
    symmetric) so every operand has a task axis to shard, and each
    shard's iteration counts come back as one entry per task shard (its
    while loops exit on their own residuals)."""
    if beta0 is None:
        beta0 = jnp.zeros_like(cs)
    if M0 is None:
        M0 = scaled_identity_m0(Sigmas)
    T, R = P(task_axis), P()
    # lam, mu and tol are scalars, replicated; tol=None (fixed budgets)
    # stays a Python None
    scalars = [jnp.asarray(v, cs.dtype) for v in (lam, mu)
               + (() if tol is None else (tol,))]

    def local(S, c, b0, m0, lam_, mu_, tol_=None):
        b, M, nl, nd = _solves(S, c, lam_, mu_, b0, m0, tol_, **iters)
        return b, M, jnp.reshape(nl, (1,)), jnp.reshape(nd, (1,))

    beta_hat, Ms, nl, nd = shard_map(
        local, mesh=mesh, in_specs=(T, T, T, T) + (R,) * len(scalars),
        out_specs=(T, T, T, T))(Sigmas, cs, beta0, M0, *scalars)
    return beta_hat, Ms, nl, nd


@partial(jax.jit, static_argnames=("lasso_iters", "debias_iters", "warm",
                                   "mesh", "task_axis"))
def refit(state: StreamState, lam, mu, Lam, lasso_iters: int = 400,
          debias_iters: int = 600, warm: bool = True, tol=None,
          mesh=None, task_axis: str = "task"
          ) -> Tuple[StreamState, RefitInfo]:
    """One DSML refresh on the state's statistics.

    Returns the new state (updated beta/M/support, generation + 1) and
    a `RefitInfo`. With `warm=True` both solves restart from the
    previous generation: the lasso from `beta_local` (an empty state's
    zeros make the first warm refit identical to a cold one) and the
    debias M solve from `Ms` (generation 0 falls back to the engine's
    scaled-identity start, selected under jit via the traced
    generation).

    `tol=` turns the iteration counts into CEILINGS: both solves early
    exit on their KKT residuals, so a warm refit under a tol costs only
    the iterations the statistics drift actually demands — the latency
    budget the serving front relies on to keep refits off the predict
    path. The iterations run come back on the info
    (`lasso_iters_run`/`debias_iters_run`).

    With a `mesh`, the solves run per shard of its `task_axis` (the
    statistics are task-sharded there, replicated over the data axis);
    everything after them — debias, threshold, drift — is partitioned
    by XLA, and the new state keeps the layout of `state_shardings`.
    The info then also carries each task shard's iteration counts.

    Each phase runs under a `jax.named_scope` — `refit.power`,
    `refit.lasso`, `refit.msolve` (with its warm start), `refit.debias`,
    `refit.threshold` — so the compiled program's ops carry their phase
    in their `op_name` metadata, and a profile can be split by phase.
    """
    beta0 = state.beta_local if warm else None
    M0 = None
    if warm:
        with jax.named_scope("refit.msolve"):
            M0 = jnp.where(state.generation > 0, state.Ms,
                           scaled_identity_m0(state.Sigmas))
    iters = dict(lasso_iters=lasso_iters, debias_iters=debias_iters)
    shard_lasso = shard_debias = None
    if mesh is None:
        beta_hat, Ms, lasso_run, debias_run = _solves(
            state.Sigmas, state.cs, lam, mu, beta0, M0, tol, **iters)
    else:
        beta_hat, Ms, shard_lasso, shard_debias = _sharded_solves(
            mesh, task_axis, state.Sigmas, state.cs, lam, mu, beta0, M0,
            tol, **iters)
        lasso_run, debias_run = jnp.max(shard_lasso), jnp.max(shard_debias)
    with jax.named_scope("refit.debias"):
        beta_u = debias_batched(state.Sigmas, state.cs, beta_hat, Ms)
    with jax.named_scope("refit.threshold"):
        support = support_from_rows(beta_u.T, Lam)
        beta_tilde = beta_u * support[None, :]
        new_state = state._replace(
            beta_local=beta_hat, Ms=Ms, beta_u=beta_u,
            beta_tilde=beta_tilde, support=support,
            generation=state.generation + 1)
        info = RefitInfo(
            jaccard=jaccard_support(support, state.support).astype(
                state.cs.dtype),
            support_size=jnp.sum(support).astype(jnp.int32),
            generation=new_state.generation,
            lasso_iters_run=jnp.asarray(lasso_run, jnp.int32),
            debias_iters_run=jnp.asarray(debias_run, jnp.int32))
    if mesh is not None:
        # the refreshed state stays where the service keeps it
        new_state = jax.lax.with_sharding_constraint(
            new_state, state_shardings(mesh, task_axis))
        info = info._replace(
            shard_lasso_iters=shard_lasso.astype(jnp.int32),
            shard_debias_iters=shard_debias.astype(jnp.int32))
    return new_state, info


@partial(jax.jit, static_argnames=("lasso_iters", "debias_iters", "warm"))
def refit_logistic(state: StreamState, Xs: jnp.ndarray, ys: jnp.ndarray,
                   lam, mu, Lam, lasso_iters: int = 600,
                   debias_iters: int = 600,
                   warm: bool = True) -> Tuple[StreamState, RefitInfo]:
    """One Section-4 (classification) DSML refresh, warm-started from
    the previous generation exactly like the regression `refit`.

    The logistic loss is not a function of the state's `(Sigma, c)`
    statistics, so the gradient re-touches a retained raw window
    `Xs (m, n, p)` / `ys (m, n) in {-1, +1}` — but the state still
    carries everything that makes consecutive refits cheap: with
    `warm=True` the batched l1-logistic solve restarts from
    `beta_local` and the weighted-Hessian debias solve from the
    previous `Ms` (generation 0 falls back to the engine's
    scaled-identity start, selected under jit via the traced
    generation). The state's regression statistics fields are left
    untouched; the model fields (`beta_local`, `Ms`, `beta_u`,
    `beta_tilde`, `support`, `generation`) advance one generation.
    """
    beta0 = state.beta_local if warm else None
    beta_hat = solve_logistic_lasso_batched(Xs, ys, lam, iters=lasso_iters,
                                            beta0=beta0)
    beta_u, Ms = debias_logistic_batched(
        Xs, ys, beta_hat, mu, iters=debias_iters,
        M0=state.Ms if warm else None,
        M0_valid=(state.generation > 0) if warm else None)
    support = support_from_rows(beta_u.T, Lam)
    beta_tilde = beta_u * support[None, :]
    new_state = state._replace(
        beta_local=beta_hat, Ms=Ms, beta_u=beta_u, beta_tilde=beta_tilde,
        support=support, generation=state.generation + 1)
    info = RefitInfo(
        jaccard=jaccard_support(support, state.support).astype(state.cs.dtype),
        support_size=jnp.sum(support).astype(jnp.int32),
        generation=new_state.generation)
    return new_state, info
