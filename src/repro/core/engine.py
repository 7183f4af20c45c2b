"""Batched sufficient-statistics solver engine.

Every l1-regularized quadratic this repo solves — the per-task lasso of
DSML step 1, the debias M-matrix estimation of step 2, the tuned lasso
sweeps of the paper benchmarks — is an instance of

    min_b  (1/2) b' Sigma b - c' b + lam ||b||_1

on precomputed sufficient statistics (Sigma, c). The engine solves a
whole BATCH of such problems (independent Sigmas, multi-RHS c) in one
accelerated FISTA loop whose hot step is the fused Pallas
`ista_step_batched` kernel — one MXU-shaped stream of tiles instead of a
vmap of m scalar solver loops. Off-TPU the step runs as one XLA batched
matmul (the kernel's jnp oracle), so CPU tests stay fast; pass
`use_kernel=True, interpret=True` to exercise the pallas path anywhere.

`core/solvers.lasso`, `core/debias.inverse_hessian_m` and
`core/dsml.dsml_fit{,_sharded}` are thin wrappers over this engine; they
reproduce the original FISTA iterates exactly (same step sizes, same
momentum schedule) because the engine works in the normalized gradient
convention g = Sigma b - c with caller-supplied per-task step sizes.

Engine v2 (DESIGN.md §10): each FISTA iteration is ONE fused kernel
dispatch (`fista_step_batched` computes the prox'd iterate and the
momentum extrapolation in the same epilogue), `tol=` adds
convergence-aware early exit on the prox-gradient KKT residual, the
kernel block policy defaults to the autotuned winner for the shape
(`kernels/autotune.py`; explicit `block=` wins), and
`solve_logistic_lasso_batched` extends the batched loop to the
Section-4 logistic path — every task's l1-logistic solve as one
all-tasks gradient instead of a vmap of per-task FISTA loops.

The sample-streaming hot paths are fused too (DESIGN.md §11): the
logistic gradient runs as the `kernels/logistic_grad` Pallas kernel
(forward matvec, sigmoid residual, and back-projection from the same
resident X tiles) and `sufficient_stats` as the `kernels/rank_update`
kernel (Sigma and c from one pass over the chunk) — both behind the
standard dispatch convention: kernel by default on TPU, bitwise jnp
oracle as the fast CPU path and the ragged-shape fallback, autotuned
default block sizes under their own `kernels/autotune.py` namespaces.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.prox import soft_threshold
from repro.core.solvers import lasso_stats_step_scale, power_iteration
from repro.kernels import common as kernel_common
from repro.kernels.ista_step.ops import (
    fista_step_batched, step_routes_to_oracle,
)
from repro.kernels.ista_step.ref import (
    fista_step_batched_ref, ista_step_batched_ref,
)
from repro.kernels.logistic_grad.ops import logistic_grad, routes_to_oracle
from repro.kernels.logistic_grad.ref import logistic_grad_ref
from repro.kernels.rank_update.ops import rank_routes_to_oracle, rank_update


def power_iteration_batched(Sigmas: jnp.ndarray, iters: int = 64) -> jnp.ndarray:
    """Largest eigenvalue per task of a (m, p, p) PSD stack."""
    return jax.vmap(partial(power_iteration, iters=iters))(Sigmas)


def _record_solve(kind: str, n_iters, ceiling: int, out, **loop) -> None:
    """Record a solve's iterations-used vs its `iters` ceiling (and the
    early-exit verdict the `tol=`/`return_iters` machinery implies).
    A lasso-loop solve also passes its `loop` (the keywords of
    `record_fista_steps`), which counts how its steps moved the carry.
    Eager-only by construction: when a caller jits a public wrapper the
    whole wrapper body runs under trace, the solve's output `out` is a
    tracer and `int(n_iters)` would scalarize one — so this is a no-op
    then (RL107 territory; RL108 additionally lint-proves no jit root
    in this module can reach an obs call)."""
    if not obs.enabled() or isinstance(out, jax.core.Tracer):
        return
    used = int(n_iters)
    obs.inc("engine.solve.calls", kind=kind)
    obs.observe("engine.solve.iters_used", used, kind=kind)
    obs.observe("engine.solve.iters_ceiling", ceiling, kind=kind)
    if used < ceiling:
        obs.inc("engine.solve.early_exit", kind=kind)
    if loop:
        record_fista_steps(kind, used, ceiling, **loop)


def fista_chunk(iters: int, tol, check_every: int) -> int:
    """Iterations `_fista_loop` runs between two residual checks: the
    whole budget without a `tol`."""
    return iters if tol is None else min(check_every, iters)


def fista_pairs(n):
    """The lasso loop's pair schedule for a chunk of n steps (a Python
    or a traced int): (pairs, unpaired steps). The steps of a pair run
    in place; an odd chunk's unpaired last step then copies its z' back
    into z's buffer, once."""
    return n // 2, n % 2


def record_fista_steps(kind: str, n_iters: int, iters: int, tol, *,
                       p: int, r: int, use_kernel: bool, block=128,
                       check_every: int = 25) -> None:
    """Count a lasso-loop solve's steps as `engine.fista_steps{kind,
    carry}`: `inplace` for the steps of pairs, whose kernel wrote the
    iterates into the loop's own buffers, `copy` for the unpaired ones,
    from the loop's chunks (whole ones, then the ceiling's truncated
    last one). Only the kernel writes in place, so a solve the jnp
    oracle ran records nothing. Eager only: `_record_solve` calls it,
    and so does the streaming service after a jitted refit, from the
    iteration counts on its `RefitInfo`."""
    if not obs.enabled() or not use_kernel \
            or step_routes_to_oracle(p, r, block):
        return
    chunk = fista_chunk(iters, tol, check_every)
    whole, rest = divmod(n_iters, max(chunk, 1))
    copied = whole * fista_pairs(chunk)[1] + fista_pairs(rest)[1]
    obs.inc("engine.fista_steps", n_iters - copied, kind=kind,
            carry="inplace")
    obs.inc("engine.fista_steps", copied, kind=kind, carry="copy")


def sufficient_stats(Xs: jnp.ndarray, ys: jnp.ndarray,
                     weights: jnp.ndarray | None = None, *,
                     use_kernel: bool | None = None,
                     interpret: bool | None = None,
                     block=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-task empirical covariance and correlation.

    Xs: (m, n, p), ys: (m, n) -> Sigmas (m, p, p), cs (m, p). These two
    arrays are ALL the data any downstream solve touches; raw (X, y)
    never re-enters the hot loop.

    `weights` (m, n) are optional per-sample weights, still normalized
    by n: Sigma_w = n^-1 X' W X, c_w = n^-1 X' W y. This is the one code
    path behind both the logistic debias Hessian (W = sigma(z)sigma(-z))
    and the streaming layer's per-sample importance weighting.

    The reduction is the fused rank-n Pallas kernel
    (`kernels/rank_update`: Sigma and c from ONE pass over the sample
    chunk) when `use_kernel` — default only on TPU; the jnp einsum
    oracle is the fast CPU path and the ragged-shape fallback. `block`
    is an int, an explicit (bp, bn) pair, or None for the autotuned
    per-shape policy (DESIGN.md §11).
    """
    m, n, p = Xs.shape
    if use_kernel is None:
        use_kernel = kernel_common.kernels_by_default()
    block = resolve_rank_block_policy(m, n, p, Xs.dtype, block, use_kernel)
    return rank_update(Xs, ys, weights, use_kernel=use_kernel,
                       interpret=interpret, block=block)


def _fista_loop(advance, init, iters, tol, check_every, residual):
    """The FISTA loop shared by the solvers. `advance(carry, n)` runs n
    iterations of a carry whose first element is the iterate x; with
    `tol=None` it runs the fixed `iters` budget as one chunk, otherwise
    `check_every`-iteration chunks of a while_loop that stops once
    `residual(x) <= tol`. The final chunk is truncated so `iters` is an
    EXACT ceiling. Returns (x, n_iters_run).

    A chunk is the unit of the lasso's pair schedule (`fista_pairs`):
    its carry (x, z, w, t) holds a spare stack w beside the momentum
    point z, because the kernel cannot write z' over z (every row block
    reads all of z); so a step writes z' into w, and the next step
    writes z'' back into z's buffer. After each pair every value is in
    its own buffer and XLA copies nothing."""
    K = fista_chunk(iters, tol, check_every)
    if tol is None:
        return advance(init, K)[0], jnp.array(iters, jnp.int32)

    def cond(state):
        _, it, res = state
        return jnp.logical_and(it < iters, res > tol)

    def chunk(state):
        carry, it, _ = state
        end = jnp.minimum(it + K, iters)
        carry = advance(carry, end - it)
        return carry, end, residual(carry[0])

    carry, n_iters, _ = jax.lax.while_loop(
        cond, chunk, (init, jnp.array(0, jnp.int32),
                      jnp.array(jnp.inf, init[0].dtype)))
    return carry[0], n_iters


def resolve_block_policy(m: int, p: int, r: int, dtype, block,
                         use_kernel: bool):
    """Engine v2 block policy: an explicit `block` (int or (bp, br, bk)
    triple) always wins; otherwise, when the kernel path is active, the
    autotuned winner for (backend, m, p, r, dtype) is looked up — the
    deterministic default on a miss: sweeps run only from eager
    `autotune.warmup_cache`, never under a trace. The oracle path never
    consults the cache."""
    from repro.kernels.ista_step.ops import (
        resolve_blocks, step_routes_to_oracle,
    )
    if block is not None:
        resolve_blocks(p, r, block)   # malformed blocks raise on EVERY
        return block                  # path, not just the kernel one
    if not use_kernel or step_routes_to_oracle(p, r):
        # the kernel dispatcher routes these shapes to the jnp oracle,
        # which ignores blocks — never consult the cache for them
        return 128
    from repro.kernels.autotune import autotune_block
    return autotune_block(m, p, r, dtype=dtype, sweep=False)


def resolve_logistic_block_policy(m: int, n: int, p: int, dtype, block,
                                  use_kernel: bool):
    """Block policy for the fused logistic-gradient kernel: an explicit
    `block` (int bn or (bn, bp) pair) wins; otherwise the autotuned
    (bn, bp) winner for (backend, m, n, p, dtype) when the kernel path
    is active. Same shape-routing caveats as `resolve_block_policy`:
    shapes the dispatcher routes to the oracle (ragged, sliver tiles,
    over the per-tile VMEM budget) never pay or pollute a sweep."""
    if block is not None:
        from repro.kernels.logistic_grad.ops import resolve_logistic_blocks
        resolve_logistic_blocks(n, p, block)   # validate on every path
        return block
    if not use_kernel or routes_to_oracle(n, p):
        return None
    from repro.kernels.autotune import autotune_logistic_block
    return autotune_logistic_block(m, n, p, dtype=dtype, sweep=False)


def resolve_rank_block_policy(m: int, n: int, p: int, dtype, block,
                              use_kernel: bool):
    """Block policy for the fused rank-n update kernel: an explicit
    `block` (int or (bp, bn) pair) wins; otherwise the autotuned winner
    for (backend, m, n, p, dtype) when the kernel path is active."""
    if block is not None:
        return block
    if not use_kernel or rank_routes_to_oracle(n, p):
        return 128
    from repro.kernels.autotune import autotune_rank_block
    return autotune_rank_block(m, n, p, dtype=dtype, sweep=False)


def solve_lasso_batched(Sigmas: jnp.ndarray, cs: jnp.ndarray, lam, *,
                        iters: int = 400, etas: jnp.ndarray | None = None,
                        beta0: jnp.ndarray | None = None,
                        use_kernel: bool | None = None,
                        interpret: bool | None = None,
                        block=None, tol=None, check_every: int = 25,
                        return_iters: bool = False) -> jnp.ndarray:
    """FISTA on a batch of sufficient-statistics lasso problems.

    Sigmas: (m, p, p); cs: (m, p) for one RHS per task or (m, p, r) for
    multi-RHS (the debias solve uses r = p with c = I). Returns an array
    shaped like `cs`.

    `etas` (m,) are per-task gradient step sizes; default 1/lambda_max
    per task. `lam` is a scalar or per-task (m,) weight; the proximal
    threshold is `etas * lam`. `beta0` warm-starts the iterates.
    `use_kernel` routes the fused step through the pallas kernel
    (default: only on TPU; the jnp batched step is the fast CPU path).

    Engine v2: every iteration is one fused prox + momentum step
    (`fista_step_batched`), bitwise-identical to the historical
    kernel-then-jnp-momentum pair. `block` is an int, an explicit
    (bp, br, bk) triple, or None for the autotuned per-shape policy.
    With `tol=` the fixed iteration budget becomes an exact ceiling:
    the loop runs in `check_every`-iteration chunks of a `while_loop`
    (final chunk truncated to the budget) and stops once the
    prox-gradient KKT residual max|x - soft(x - eta(Sigma x - c),
    eta lam)| drops to `tol`. `return_iters` additionally returns the
    number of iterations actually run. On the kernel path the steps
    write their iterates in place (`_solve_lasso_batched`); the counter
    `engine.fista_steps` says how many did.
    """
    m, p = cs.shape[:2]
    r = 1 if cs.ndim == 2 else cs.shape[-1]
    if use_kernel is None:
        use_kernel = kernel_common.kernels_by_default()
    block = resolve_block_policy(m, p, r, cs.dtype, block, use_kernel)
    out, n_iters = _solve_lasso_batched(
        Sigmas, cs, lam, etas, beta0, tol, iters=iters,
        use_kernel=use_kernel, interpret=interpret, block=block,
        check_every=check_every)
    _record_solve("lasso", n_iters, iters, out, tol=tol,
                  check_every=check_every, p=p, r=r,
                  use_kernel=use_kernel, block=block)
    return (out, n_iters) if return_iters else out


@partial(jax.jit, static_argnames=("iters", "use_kernel", "interpret",
                                   "block", "check_every"))
def _solve_lasso_batched(Sigmas, cs, lam, etas, beta0, tol, *, iters,
                         use_kernel, interpret, block, check_every):
    """The lasso loop. On the kernel path each step writes x' over x
    and z' over a spare stack w (`fista_step_batched_inplace_pallas`).
    z' cannot overwrite z: z is the step's contraction operand, and
    every row block of the step reads all of it. So the carry is
    (x, z, w, t), and steps run in pairs (`fista_pairs`): the first
    writes z' into w, the second reads w and writes z'' into z's
    buffer, which the first has finished reading. After a pair each
    value is back in its own buffer and the loop's carry is never
    copied; only an odd chunk's unpaired last step copies its z' back
    into z's buffer. The iterates are bitwise those of one step per
    iteration. The jnp oracle ignores w."""
    squeeze = cs.ndim == 2
    C = cs[..., None] if squeeze else cs
    m = C.shape[0]
    if etas is None:
        etas = 1.0 / jnp.maximum(power_iteration_batched(Sigmas), 1e-12)
    etas = jnp.broadcast_to(jnp.asarray(etas, C.dtype).reshape(-1), (m,))

    if use_kernel:
        step = lambda Z, X, W, theta: fista_step_batched(
            Sigmas, Z, X, W, C, etas, lam, theta, block=block,
            interpret=interpret)
    else:
        step = lambda Z, X, W, theta: fista_step_batched_ref(
            Sigmas, Z, X, C, etas, lam, theta)

    if beta0 is None:
        X0 = jnp.zeros_like(C)
    else:
        b0 = beta0[..., None] if beta0.ndim == C.ndim - 1 else beta0
        X0 = jnp.broadcast_to(b0, C.shape).astype(C.dtype)

    def body(carry):
        # x' over x, z' into the spare; the old z becomes the spare
        x, z, w, t = carry
        t_next = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        x_next, z_next = step(z, x, w, (t - 1.0) / t_next)
        return x_next, z_next, z, t_next

    def unpaired_step(carry):
        # z' back into z's buffer (one copy); the spare's value is moot
        x, z, _, t = body(carry)
        return x, z, z, t

    def advance(carry, n):
        pairs, unpaired = fista_pairs(n)
        carry = jax.lax.fori_loop(0, pairs, lambda _, c: body(body(c)),
                                  carry)
        return jax.lax.fori_loop(0, unpaired,
                                 lambda _, c: unpaired_step(c), carry)

    def residual(x):
        # prox-gradient KKT residual: zero iff x is the lasso optimum
        x_fp = ista_step_batched_ref(Sigmas, x, C, etas, lam)
        return jnp.max(jnp.abs(x_fp - x))

    init = (X0, X0, jnp.zeros_like(X0), jnp.array(1.0, C.dtype))
    x, n_iters = _fista_loop(advance, init, iters, tol, check_every,
                             residual)
    return (x[..., 0] if squeeze else x), n_iters


def solve_lasso_grid(Sigmas: jnp.ndarray, cs: jnp.ndarray,
                     lams: jnp.ndarray, *, iters: int = 400,
                     etas: jnp.ndarray | None = None,
                     use_kernel: bool | None = None,
                     interpret: bool | None = None,
                     block=None) -> jnp.ndarray:
    """Solve every (task, lambda) pair of a tuning grid in ONE batch.

    Sigmas (m, p, p), cs (m, p), lams (k,) -> (k, m, p). The engine
    takes per-task regularization weights, so a lambda grid is just k*m
    tasks sharing tiled statistics — the whole regularization-path sweep
    (lam = 0 included) costs one engine call instead of k solver runs.
    Step sizes depend only on Sigma and are shared across the grid.

    Like every public engine entry point this is an EAGER wrapper over
    a jitted inner solve: policy resolution (backend default, autotune
    lookup) and telemetry happen out here with concrete values, the
    math compiles once in `_solve_lasso_grid`.
    """
    m, p = cs.shape
    lams = jnp.asarray(lams, cs.dtype)
    k = lams.shape[0]
    if use_kernel is None:
        use_kernel = kernel_common.kernels_by_default()
    block = resolve_block_policy(k * m, p, 1, cs.dtype, block, use_kernel)
    B = _solve_lasso_grid(Sigmas, cs, lams, etas, iters=iters,
                          use_kernel=use_kernel, interpret=interpret,
                          block=block)
    _record_solve("lasso_grid", iters, iters, B, tol=None, p=p, r=1,
                  use_kernel=use_kernel, block=block)
    return B


@partial(jax.jit, static_argnames=("iters", "use_kernel", "interpret",
                                   "block"))
def _solve_lasso_grid(Sigmas, cs, lams, etas, *, iters, use_kernel,
                      interpret, block):
    m, p = cs.shape
    lams = jnp.asarray(lams, cs.dtype)
    k = lams.shape[0]
    if etas is None:
        etas = 1.0 / jnp.maximum(power_iteration_batched(Sigmas), 1e-12)
    Sig_g = jnp.tile(Sigmas, (k, 1, 1))
    cs_g = jnp.tile(cs, (k, 1))
    etas_g = jnp.tile(jnp.asarray(etas, cs.dtype).reshape(-1), (k,))
    lam_g = jnp.repeat(lams, m)
    B, _ = _solve_lasso_batched(Sig_g, cs_g, lam_g, etas_g, None, None,
                                iters=iters, use_kernel=use_kernel,
                                interpret=interpret, block=block,
                                check_every=25)
    return B.reshape(k, m, p)


def solve_lasso_eq2(Sigmas: jnp.ndarray, cs: jnp.ndarray, lam, *,
                    iters: int = 400,
                    beta0: jnp.ndarray | None = None,
                    lam_max: jnp.ndarray | None = None,
                    tol=None, check_every: int = 25,
                    use_kernel: bool | None = None,
                    return_iters: bool = False) -> jnp.ndarray:
    """Batched lasso in the PAPER'S eq.-2 convention:

        (1/n)||y_t - X_t b||^2 + lam ||b||_1

    on sufficient statistics. Owns the translation into the engine's
    normalized-gradient convention — step 2/max(2*lambda_max, eps),
    threshold weight lam/2 — so callers can never mismatch the pair
    (passing an unhalved lam with the eq.-2 step runs at double the
    intended regularization with no error). `beta0` (m, p) warm-starts
    the FISTA iterates (streaming refits restart from the previous
    solution). `lam_max` (m,) are precomputed per-task largest
    eigenvalues; callers that also run the debias solve pass one shared
    power iteration instead of paying it twice.

    `tol=` turns `iters` into an exact CEILING via the engine's
    chunked-while-loop early exit (prox-gradient KKT residual checked
    every `check_every` iterations) — this is the latency-budget lever
    the streaming refit path leans on: a warm-started refit under a tol
    exits in a fraction of the ceiling, and the ceiling bounds the
    worst case. `return_iters` also returns the iterations run.
    `use_kernel` as in `solve_lasso_batched` (default: only on TPU)."""
    m, p = cs.shape
    if use_kernel is None:
        use_kernel = kernel_common.kernels_by_default()
    block = resolve_block_policy(m, p, 1, cs.dtype, None, use_kernel)
    out, n_iters = _solve_lasso_eq2(Sigmas, cs, lam, beta0, lam_max, tol,
                                    iters=iters, use_kernel=use_kernel,
                                    block=block, check_every=check_every)
    _record_solve("lasso_eq2", n_iters, iters, out, tol=tol,
                  check_every=check_every, p=p, r=1,
                  use_kernel=use_kernel, block=block)
    return (out, n_iters) if return_iters else out


@partial(jax.jit, static_argnames=("iters", "use_kernel", "block",
                                   "check_every"))
def _solve_lasso_eq2(Sigmas, cs, lam, beta0, lam_max, tol, *, iters,
                     use_kernel, block, check_every):
    if lam_max is None:
        etas = jax.vmap(lasso_stats_step_scale)(Sigmas)
    else:
        etas = 2.0 / jnp.maximum(2.0 * lam_max, 1e-12)
    return _solve_lasso_batched(Sigmas, cs, 0.5 * jnp.asarray(lam),
                                etas, beta0, tol, iters=iters,
                                use_kernel=use_kernel, interpret=None,
                                block=block, check_every=check_every)


def solve_lasso_eq2_grid(Sigmas: jnp.ndarray, cs: jnp.ndarray, lams, *,
                         iters: int = 400) -> jnp.ndarray:
    """`solve_lasso_grid` in the paper's eq.-2 convention (see
    `solve_lasso_eq2`). Sigmas (m, p, p), cs (m, p), lams (k,) ->
    (k, m, p)."""
    m, p = cs.shape
    lams = jnp.asarray(lams, cs.dtype)
    k = lams.shape[0]
    use_kernel = kernel_common.kernels_by_default()
    block = resolve_block_policy(k * m, p, 1, cs.dtype, None, use_kernel)
    out = _solve_lasso_eq2_grid(Sigmas, cs, lams, iters=iters,
                                use_kernel=use_kernel, block=block)
    _record_solve("lasso_eq2_grid", iters, iters, out, tol=None, p=p,
                  r=1, use_kernel=use_kernel, block=block)
    return out


@partial(jax.jit, static_argnames=("iters", "use_kernel", "block"))
def _solve_lasso_eq2_grid(Sigmas, cs, lams, *, iters, use_kernel, block):
    etas = jax.vmap(lasso_stats_step_scale)(Sigmas)
    return _solve_lasso_grid(Sigmas, cs, 0.5 * lams, etas, iters=iters,
                             use_kernel=use_kernel, interpret=None,
                             block=block)


def solve_logistic_lasso_batched(Xs: jnp.ndarray, ys: jnp.ndarray, lam, *,
                                 iters: int = 600,
                                 etas: jnp.ndarray | None = None,
                                 beta0: jnp.ndarray | None = None,
                                 grad_scale=1.0, prox=None,
                                 momentum: bool = True, tol=None,
                                 check_every: int = 25,
                                 use_kernel: bool | None = None,
                                 interpret: bool | None = None,
                                 block=None,
                                 return_iters: bool = False):
    """One FISTA loop for a whole batch of l1-logistic regressions.

    Xs (m, n, p), ys (m, n) in {-1, +1}; lam scalar or per-task (m,).
    Returns B (m, p). The logistic loss is not a function of (Sigma, c)
    alone, so the gradient re-touches the raw samples — but as ONE
    all-tasks gradient `-X'(y sigmoid(-y Xb))/n` per iteration instead
    of a vmap of m per-task FISTA loops, with per-task step sizes
    `1 / max(lambda_max(Sigma)/4, eps)` from one shared batched power
    iteration (the logistic Hessian is bounded by Sigma/4). On the
    kernel path (`use_kernel`, default only on TPU) the gradient is the
    fused Pallas `kernels/logistic_grad` kernel — forward matvec,
    sigmoid residual, and back-projection in one dispatch over each
    resident X slab (feature-tiled past the VMEM budget, so the p >> n
    regime stays on the kernel); otherwise it is the bitwise-identical
    jnp einsum oracle (the fast CPU path). `block` is an int sample
    tile bn, a (bn, bp) pair, or None for the autotuned per-shape
    policy (DESIGN.md §11-§12).

    `beta0` (m, p) warm-starts the iterates (streaming refits restart
    from the previous generation). `prox` overrides the elementwise
    soft threshold — signature `prox(B (m, p), steps (m, 1)) -> (m, p)`
    — which is how the group-lasso / iCAP / masked-refit variants reuse
    this loop. `prox` is a STATIC jit argument hashed by identity:
    when calling eagerly in a loop, pass one reused function object
    (not a fresh lambda per call) or every call retraces. `grad_scale`
    rescales the gradient (the multi-task objectives divide by m);
    `momentum=False` degrades FISTA to plain proximal gradient (the
    masked refit's historical iteration). As in
    `solve_lasso_batched`, `tol=` stops early on the prox-gradient
    fixed-point residual every `check_every` iterations, and
    `return_iters` also returns the iterations run.
    """
    m, n, p = Xs.shape
    if use_kernel is None:
        use_kernel = kernel_common.kernels_by_default()
    block = resolve_logistic_block_policy(m, n, p, Xs.dtype, block,
                                          use_kernel)
    out, n_iters = _solve_logistic_lasso_batched(
        Xs, ys, lam, etas, beta0, grad_scale, tol, iters=iters, prox=prox,
        momentum=momentum, check_every=check_every, use_kernel=use_kernel,
        interpret=interpret, block=block)
    _record_solve("logistic", n_iters, iters, out)
    return (out, n_iters) if return_iters else out


@partial(jax.jit, static_argnames=("iters", "momentum", "prox",
                                   "check_every", "use_kernel",
                                   "interpret", "block"))
def _solve_logistic_lasso_batched(Xs, ys, lam, etas, beta0, grad_scale,
                                  tol, *, iters, prox, momentum,
                                  check_every, use_kernel, interpret,
                                  block):
    m, n, p = Xs.shape
    lam_t = jnp.broadcast_to(jnp.asarray(lam, Xs.dtype).reshape(-1), (m,))
    if etas is None:
        Sigmas, _ = sufficient_stats(Xs, ys)
        L = 0.25 * power_iteration_batched(Sigmas)
        etas = 1.0 / jnp.maximum(L, 1e-12)
    S = jnp.broadcast_to(jnp.asarray(etas, Xs.dtype).reshape(-1),
                         (m,))[:, None]

    if use_kernel:
        graw = lambda B: logistic_grad(Xs, ys, B, block=block,
                                       interpret=interpret)
    else:
        graw = lambda B: logistic_grad_ref(Xs, ys, B)
    grad = lambda B: graw(B) * grad_scale

    if prox is None:
        prox = lambda V, steps: soft_threshold(V, steps * lam_t[:, None])

    X0 = jnp.zeros((m, p), Xs.dtype) if beta0 is None \
        else beta0.astype(Xs.dtype)

    def body(_, carry):
        x, z, t = carry
        t_next = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        x_next = prox(z - S * grad(z), S)
        z_next = x_next + ((t - 1.0) / t_next) * (x_next - x) \
            if momentum else x_next
        return x_next, z_next, t_next

    def advance(carry, n):
        return jax.lax.fori_loop(0, n, body, carry)

    def residual(x):
        return jnp.max(jnp.abs(prox(x - S * grad(x), S) - x))

    return _fista_loop(advance, (X0, X0, jnp.array(1.0, Xs.dtype)),
                       iters, tol, check_every, residual)


def debias_batched(Sigmas: jnp.ndarray, cs: jnp.ndarray,
                   beta_hat: jnp.ndarray, Ms: jnp.ndarray) -> jnp.ndarray:
    """Debiased estimates (paper eq. 4) from sufficient statistics:

        b_u = b + M (c - Sigma b)        [ = b + n^-1 M X'(y - X b) ]

    Sigmas (m, p, p), cs/beta_hat (m, p), Ms (m, p, p) -> (m, p).
    """
    resid_corr = cs - jnp.einsum("tij,tj->ti", Sigmas, beta_hat)
    return beta_hat + jnp.einsum("tij,tj->ti", Ms, resid_corr)


def scaled_identity_m0(Sigmas: jnp.ndarray) -> jnp.ndarray:
    """Default M warm start: identity scaled by 1/diag(Sigma) per task
    (diagonal, so it is its own transpose in either M/C convention)."""
    m, p, _ = Sigmas.shape
    eye = jnp.broadcast_to(jnp.eye(p, dtype=Sigmas.dtype), (m, p, p))
    return eye / jnp.maximum(
        jnp.diagonal(Sigmas, axis1=-2, axis2=-1), 1e-12)[:, None, :]


def inverse_hessian_batched(Sigmas: jnp.ndarray, mu, iters: int = 600,
                            M0: jnp.ndarray | None = None,
                            lam_max: jnp.ndarray | None = None,
                            tol=None, check_every: int = 25,
                            use_kernel: bool | None = None,
                            return_iters: bool = False) -> jnp.ndarray:
    """Approximate inverse Ms (m, p, p) of a stack of PSD covariances —
    the Javanmard-Montanari program for all tasks and all p rows as ONE
    multi-RHS batched solve (m*p right-hand sides). `M0` warm-starts the
    solve (e.g. the previous generation's Ms in a streaming refit);
    default is the scaled identity of the single-task solver. `lam_max`
    (m,) lets callers share one power iteration with the lasso solve.
    `tol=` makes `iters` a ceiling (early exit on the KKT residual,
    checked every `check_every` iterations) so a warm-started streaming
    refit pays only the iterations it needs; `return_iters` also
    returns the iterations run. `use_kernel` as in
    `solve_lasso_batched` (default: only on TPU)."""
    m, p, _ = Sigmas.shape
    if use_kernel is None:
        use_kernel = kernel_common.kernels_by_default()
    block = resolve_block_policy(m, p, p, Sigmas.dtype, None, use_kernel)
    out, n_iters = _inverse_hessian_batched(
        Sigmas, mu, M0, lam_max, tol, iters=iters,
        use_kernel=use_kernel, block=block, check_every=check_every)
    _record_solve("debias", n_iters, iters, out, tol=tol,
                  check_every=check_every, p=p, r=p,
                  use_kernel=use_kernel, block=block)
    return (out, n_iters) if return_iters else out


@partial(jax.jit, static_argnames=("iters", "use_kernel", "block",
                                   "check_every"))
def _inverse_hessian_batched(Sigmas, mu, M0, lam_max, tol, *, iters,
                             use_kernel, block, check_every):
    m, p, _ = Sigmas.shape
    if lam_max is None:
        lam_max = power_iteration_batched(Sigmas)
    etas = 1.0 / jnp.maximum(lam_max, 1e-12)
    eye = jnp.broadcast_to(jnp.eye(p, dtype=Sigmas.dtype), (m, p, p))
    C0 = scaled_identity_m0(Sigmas) if M0 is None else \
        jnp.swapaxes(M0, -1, -2)
    Cs, n_iters = _solve_lasso_batched(Sigmas, eye, mu, etas, C0, tol,
                                       iters=iters, use_kernel=use_kernel,
                                       interpret=None, block=block,
                                       check_every=check_every)
    return jnp.swapaxes(Cs, -1, -2), n_iters
