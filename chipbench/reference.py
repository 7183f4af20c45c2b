"""Plain reference for the streaming DSML service, in `jax.numpy` alone.

It imports nothing of the program under test. Given the benchmark's own
data (the chunk pool made from the seed, and the sequence in which the
stream handed the chunks over) it computes what the service should hold
and serve:

* `fold_stats`: the running means Sigma_t = X_t'X_t / N and
  c_t = X_t'y_t / N over every folded row, with the mean of y_t^2 for
  scaling. Each distinct pool chunk is reduced once and weighted by how
  often the stream folded it, so the cost does not grow with the window.
* `solve`, `threshold`: one cold DSML fit on those statistics, by plain
  FISTA:
  the eq.-2 lasso min b'Sigma b/2 - c'b + (lam/2)|b|_1 per task, the
  Javanmard-Montanari rows min m'Sigma m/2 - e_i'm + mu|m|_1 for every
  i, the debiased b_u = b + M(c - Sigma b), the shared support
  {j : ||b_u[:, j]||_2 > Lam} and b_tilde = b_u on that support.
* `lasso_kkt`, `debias_kkt`: how far a given lasso solution or debias
  matrix is from satisfying its optimality conditions on given
  statistics.
* `scores`: predict scores x'b_tilde.

Arrays are float32 and every product accumulates in float32 at
`Precision.HIGHEST`: that is the reference. The fold, the fit and the
scores also take `operands`, a type that every operand is rounded to
before each product: the control is the same arithmetic one precision
step below what the configuration states, put in the program's place;
the comparison must fail it.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _ein(spec, *ops, operands=None):
    if operands is not None:
        ops = [o.astype(operands).astype(jnp.float32) for o in ops]
    return jnp.einsum(spec, *ops, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


# -- the data -------------------------------------------------------------

def ar_cholesky(p: int, rho: float):
    """Lower Cholesky factor of the AR(rho) covariance rho^|a-b|."""
    idx = jnp.arange(p)
    cov = rho ** jnp.abs(idx[:, None] - idx[None, :]).astype(jnp.float32)
    return jnp.linalg.cholesky(cov + 1e-9 * jnp.eye(p, dtype=jnp.float32))


@partial(jax.jit, static_argnames=("m", "p", "s", "low", "high"))
def coefficients(key, *, m, p, s, low, high):
    """B (p, m) with a shared support of s rows, U(low, high) on it."""
    k_sup, k_val = jax.random.split(key)
    support = jnp.zeros(p, bool).at[jax.random.permutation(k_sup, p)[:s]].set(
        True)
    vals = jax.random.uniform(k_val, (p, m), minval=low, maxval=high)
    return vals * support[:, None], support


@partial(jax.jit, static_argnames=("m", "n", "noise"))
def chunk(key, chol, B, *, m, n, noise):
    """One (m, n, p) design chunk from the AR design and its responses."""
    k_x, k_e = jax.random.split(key)
    p = chol.shape[0]
    X = jax.random.normal(k_x, (m, n, p)) @ chol.T
    y = jnp.einsum("tnp,pt->tn", X, B) + noise * jax.random.normal(k_e, (m, n))
    return X, y


@partial(jax.jit, static_argnames=("rows",))
def design_rows(key, chol, *, rows):
    """Predict rows drawn from the same AR design."""
    return jax.random.normal(key, (rows, chol.shape[0])) @ chol.T


# -- the fold -------------------------------------------------------------

@partial(jax.jit, static_argnames=("operands",))
def _chunk_sums(X, y, *, operands):
    return (_ein("tni,tnj->tij", X, X, operands=operands),
            _ein("tni,tn->ti", X, y, operands=operands),
            jnp.sum(y * y, axis=1))


@partial(jax.jit, donate_argnums=(0,))
def _accumulate(acc, sums, w):
    return tuple(a + w * s for a, s in zip(acc, sums))


def fold_stats(pool_X, pool_y, sequence, *, operands=None):
    """Running means (Sigma, c, mean y^2) after folding `sequence` (pool
    indices in the order folded), as device arrays."""
    m, n, p = pool_X.shape[1:]
    counts = np.bincount(np.asarray(sequence, np.int64),
                         minlength=len(pool_X))
    acc = (jnp.zeros((m, p, p)), jnp.zeros((m, p)), jnp.zeros((m,)))
    for k in np.flatnonzero(counts):
        sums = _chunk_sums(jnp.asarray(pool_X[k]), jnp.asarray(pool_y[k]),
                           operands=operands)
        acc = _accumulate(acc, sums, float(counts[k]))
        del sums
    rows = float(counts.sum() * n)
    return tuple(a / rows for a in acc)


# -- the fit --------------------------------------------------------------

def _soft(v, tau):
    return jnp.sign(v) * jnp.maximum(jnp.abs(v) - tau, 0.0)


def _largest_eig(S, iters, operands):
    m, p, _ = S.shape
    v0 = jnp.full((m, p), 1.0 / math.sqrt(p))

    def body(_, v):
        w = _ein("tij,tj->ti", S, v, operands=operands)
        return w / jnp.maximum(jnp.linalg.norm(w, axis=1, keepdims=True),
                               1e-30)

    v = jax.lax.fori_loop(0, iters, body, v0)
    return jnp.sum(v * _ein("tij,tj->ti", S, v, operands=operands), axis=1)


def _fista(S, C, tau, eta, X0, iters, operands):
    """FISTA for min x'Sx/2 - C'x + tau|x|_1, per task and column:
    S (m, p, p), C and X0 (m, p, r), eta (m,)."""
    eta = eta.reshape(-1, 1, 1)

    def body(_, carry):
        x, z, t = carry
        g = _ein("tij,tjr->tir", S, z, operands=operands) - C
        x_new = _soft(z - eta * g, eta * tau)
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        z = x_new + ((t - 1.0) / t_new) * (x_new - x)
        return x_new, z, t_new

    x, _, _ = jax.lax.fori_loop(0, iters, body,
                                (X0, X0, jnp.asarray(1.0, jnp.float32)))
    return x


@partial(jax.jit, static_argnames=("power_iters", "lasso_iters",
                                   "debias_iters", "operands"))
def solve(S, c, lam, mu, *, power_iters, lasso_iters, debias_iters,
          operands=None):
    """The lasso b (m, p), the debias matrices M (m, p, p) and the
    debiased estimates b_u (m, p) of the tasks of (S, c)."""
    m, p, _ = S.shape
    # a step a little under 1/lambda_max: power iteration approaches the
    # largest eigenvalue from below, and the optimum does not depend on it
    eta = 1.0 / (1.05 * jnp.maximum(
        _largest_eig(S, power_iters, operands), 1e-12))
    b = _fista(S, c[..., None], lam / 2, eta, jnp.zeros((m, p, 1)),
               lasso_iters, operands)[..., 0]
    eye = jnp.eye(p)[None]
    diag = jnp.maximum(jnp.diagonal(S, axis1=1, axis2=2), 1e-12)
    Ccols = _fista(S, jnp.broadcast_to(eye, S.shape), mu, eta,
                   eye / diag[:, None, :], debias_iters, operands)
    M = jnp.swapaxes(Ccols, 1, 2)
    resid = c - _ein("tij,tj->ti", S, b, operands=operands)
    return b, M, b + _ein("tij,tj->ti", M, resid, operands=operands)


def threshold(b_u, Lam):
    """b_tilde, b_u on the shared support {j : ||b_u[:, j]||_2 > Lam},
    and the support."""
    support = jnp.linalg.norm(b_u, axis=0) > Lam
    return b_u * support[None, :], support


# -- optimality of a given solution ----------------------------------------

def _kkt(grad, x, tau):
    """Largest violation of the lasso optimality conditions
    grad + tau sign(x) = 0 where x != 0 and |grad| <= tau where x = 0,
    over tau."""
    viol = jnp.where(x != 0, jnp.abs(grad + tau * jnp.sign(x)),
                     jnp.maximum(jnp.abs(grad) - tau, 0.0))
    return jnp.max(viol) / tau


@jax.jit
def lasso_kkt(S, c, b, lam):
    """KKT violation of b as the lasso min b'Sb/2 - c'b + (lam/2)|b|_1."""
    return _kkt(_ein("tij,tj->ti", S, b) - c, b, lam / 2)


@jax.jit
def debias_kkt(S, M, mu):
    """KKT violation of the rows of M as min m'Sm/2 - e_i'm + mu|m|_1."""
    G = _ein("tik,tkj->tij", M, S) - jnp.eye(S.shape[1])[None]
    return _kkt(G, M, mu)


def scores(rows, b_tilde, *, operands=None):
    """Scores (requests, m) of host rows (requests, p) under b_tilde
    (m, p), on the device."""
    return np.asarray(_ein("np,tp->nt", jnp.asarray(rows, jnp.float32),
                           jnp.asarray(b_tilde, jnp.float32),
                           operands=operands), np.float64)
