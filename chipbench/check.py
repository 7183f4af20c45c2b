"""The comparison that decides `correct`.

A run hands over what the timed path produced: the service's statistics
after the window; the model of the generation that closed the window,
which the last refit fitted on exactly those statistics (the lasso b, the
debias matrices M, the debiased b_u, b_tilde and the support); every
published generation's b_tilde; and a sample, drawn from the seed, of
the served responses with the row each scored and the generation it
names. The plain reference (`reference.py`) folds the benchmark's own
chunks in the order the stream handed them over, and holds each step of
the timed path to it:

* `stats_gap`: the fold. The largest statistics error, |dSigma_ij| over
  sqrt(Sigma_ii Sigma_jj) and |dc_j| over sqrt(Sigma_jj mean(y^2)).
* `lasso_kkt`: the lasso. The largest violation of its optimality
  conditions by the program's b on the reference statistics, over the
  penalty lam/2.
* `debias_kkt`: the M solve. The same for the rows of M, over mu.
* `debias_gap`: the debias step. The largest |b_u - (b + M(c - Sigma b))|
  on the reference statistics.
* `threshold_diff`: the threshold. Entries of the support and of b_tilde
  that differ from the reference's threshold of the program's b_u (exact).
* `serve_gap`: serving. The largest error of a sampled served score
  against the float64 product of its row with the b_tilde of the
  generation it names, over sum_j |x_j b_j|.
* `unknown_generation`: sampled responses naming a generation that was
  never published (exact).
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference

HERE = os.path.dirname(os.path.abspath(__file__))
NAMES = ("stats_gap", "lasso_kkt", "debias_kkt", "debias_gap",
         "threshold_diff", "serve_gap", "unknown_generation")
SERVED_SAMPLE = 4096        # responses compared per run


def load_limits(cell: str) -> dict:
    with open(os.path.join(HERE, "limits", f"{cell}.json")) as f:
        return json.load(f)["limits"]


@jax.jit
def _stats_gap(S, c, S_ref, c_ref, yy_ref):
    S, c = S.astype(jnp.float32), c.astype(jnp.float32)
    d = jnp.sqrt(jnp.maximum(jnp.diagonal(S_ref, axis1=1, axis2=2), 1e-30))
    s_err = jnp.abs(S - S_ref) / (d[:, :, None] * d[:, None, :])
    c_err = jnp.abs(c - c_ref) / (d * jnp.sqrt(jnp.maximum(yy_ref, 1e-30))
                                  [:, None])
    return jnp.maximum(jnp.max(s_err), jnp.max(c_err))


@jax.jit
def _debias_gap(S, c, b, M, b_u):
    want = b + reference._ein("tij,tj->ti", M,
                              c - reference._ein("tij,tj->ti", S, b))
    return jnp.max(jnp.abs(want - b_u))


def _blocks(m, block):
    return [slice(i, i + block) for i in range(0, m, block)]


def model_numbers(S, c, model, lam, mu, Lam, block) -> dict:
    """The refit's numbers: `model` (b, M, b_u, b_tilde, support) as
    host arrays, held to the reference statistics (S, c) on the device,
    `block` tasks at a time."""
    b, M, b_u, b_tilde, support = model
    lk = dk = dg = 0.0
    for t in _blocks(S.shape[0], block):
        b_t, M_t, u_t = (jnp.asarray(b[t], jnp.float32),
                         jnp.asarray(M[t], jnp.float32),
                         jnp.asarray(b_u[t], jnp.float32))
        lk = max(lk, float(reference.lasso_kkt(S[t], c[t], b_t, lam)))
        dk = max(dk, float(reference.debias_kkt(S[t], M_t, mu)))
        dg = max(dg, float(_debias_gap(S[t], c[t], b_t, M_t, u_t)))
        del M_t
    want_b, want_sup = reference.threshold(jnp.asarray(b_u, jnp.float32), Lam)
    diff = (np.sum(np.asarray(want_sup) != np.asarray(support))
            + np.sum(np.asarray(want_b) != np.asarray(b_tilde, np.float32)))
    return {"lasso_kkt": lk, "debias_kkt": dk, "debias_gap": dg,
            "threshold_diff": int(diff)}


def serve_gap(rows, served, b) -> float:
    """rows (k, p) scored by one generation's b (m, p), served (k, m);
    float64 on the host."""
    rows, b = np.asarray(rows, np.float64), np.asarray(b, np.float64)
    scale = np.abs(rows) @ np.abs(b).T + 1e-12
    return float(np.max(np.abs(np.asarray(served, np.float64) - rows @ b.T)
                        / scale))


def served_numbers(out, pool_rows, scores_of=None) -> dict:
    """serve_gap and unknown_generation of the sampled responses;
    `scores_of(rows, b_tilde)` stands in for the program's scores."""
    gens = out["served_generations"]
    gap = float("nan") if not len(gens) else 0.0
    for g in np.unique(gens):
        if int(g) not in out["published"]:
            continue
        sel = gens == g
        rows, b = pool_rows[out["served_rows"][sel]], out["published"][int(g)]
        served = (out["served_scores"][sel] if scores_of is None
                  else scores_of(rows, b))
        gap = max(gap, serve_gap(rows, served, b))
    unknown = sum(int(g) not in out["published"] for g in gens)
    return {"serve_gap": gap, "unknown_generation": unknown}


def compare(out, pool, cfg, *, control=None) -> dict:
    """The numbers of one run. `out` is the run's outputs (see
    `traffic/stream.py`), `pool` its (X, y, rows) host data, `cfg` the
    configuration. With `control` (a type that every operand is rounded
    to) the reference in that precision stands in for the program: its
    fold, a cold fit on its statistics, and its scores."""
    pool_X, pool_y, pool_rows = pool
    ref_cfg = cfg["reference"]
    block = ref_cfg.get("task_block") or cfg["m"]
    lam, mu, Lam = penalties(cfg)
    S_ref, c_ref, yy_ref = reference.fold_stats(pool_X, pool_y,
                                                out["sequence"])
    scores_of = None
    if control is None:
        S, c = out["Sigmas"], out["cs"]
        model = tuple(out[k] for k in ("beta_local", "Ms", "beta_u",
                                       "beta_tilde", "support"))
    else:
        S, c, _ = reference.fold_stats(pool_X, pool_y, out["sequence"],
                                       operands=control)
        parts = [[np.asarray(x) for x in reference.solve(
            S[t], c[t], lam, mu, power_iters=ref_cfg["power_iters"],
            lasso_iters=ref_cfg["lasso_iters"],
            debias_iters=ref_cfg["debias_iters"], operands=control)]
            for t in _blocks(cfg["m"], block)]
        b, M, b_u = (np.concatenate([part[i] for part in parts])
                     for i in range(3))
        del parts
        b_tilde, support = reference.threshold(jnp.asarray(b_u), Lam)
        model = (b, M, b_u, np.asarray(b_tilde), np.asarray(support))

        def scores_of(rows, b_g):
            return reference.scores(rows, b_g, operands=control)
    numbers = {"stats_gap": float(_stats_gap(jnp.asarray(S), jnp.asarray(c),
                                             S_ref, c_ref, yy_ref))}
    del S, c
    numbers.update(model_numbers(S_ref, c_ref, model, lam, mu, Lam, block))
    numbers.update(served_numbers(out, pool_rows, scores_of))
    return numbers


def penalties(cfg) -> tuple:
    """lam and mu at the sqrt(log p / n) rate of `penalty_rows` rows, and
    Lam = 0.2 sqrt(m): between the per-task noise of the debiased rows
    and the smallest coefficient, scaled by the sqrt(m) of a row norm
    over tasks."""
    m, p = cfg["m"], cfg["p"]
    base = (np.log(p) / cfg["service"]["penalty_rows"]) ** 0.5
    return 4.0 * float(base), float(base), 0.2 * float(m) ** 0.5


def verdict(numbers: dict, limits: dict) -> bool:
    """Every number within its limit; a number that is not a number
    (nothing to compare) fails."""
    return all(np.isfinite(numbers[k]) and numbers[k] <= limits[k]
               for k in NAMES)
