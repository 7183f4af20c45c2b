"""Engine v2 tests: fused-momentum FISTA kernel, convergence-aware
early exit, the batched logistic solver path, and block-size autotuning.

Contracts (ISSUE 3 / DESIGN.md §10):
  * the fused-momentum kernel reproduces the historical two-op
    (kernel step + separate jnp momentum) iterates bitwise in
    interpret mode, and the engine's CPU oracle path reproduces the
    historical ref-step loop bitwise;
  * the in-place pair schedule reproduces the two-output loop's
    iterates and iteration counts bitwise, and `engine.fista_steps`
    counts its in-place and copied steps;
  * `tol=` early exit stops before the iteration ceiling and matches
    the full-budget solution to 1e-5;
  * `solve_logistic_lasso_batched` matches the per-task FISTA loops it
    replaced to 1e-5 for k ∈ {1, 3, 8} tasks, and every logistic
    entry point (dsml_logistic_fit, group/icap, masked refit) matches
    its historical per-task implementation;
  * the autotune cache round-trips (second lookup never re-times) and
    explicit `block=` bypasses it entirely.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    dsml_logistic_fit, gen_classification, gen_regression,
    group_logistic_lasso, icap_logistic, logistic_lasso,
    refit_logistic_masked, solve_lasso_batched,
    solve_logistic_lasso_batched, sufficient_stats,
)
from repro.core.prox import group_soft_threshold, prox_linf, soft_threshold
from repro.core.solvers import fista, power_iteration
from repro.kernels.ista_step.kernel import fista_step_batched_pallas
from repro.kernels.ista_step.ops import ista_step_batched
from repro.kernels.ista_step.ref import ista_step_batched_ref

KEY = jax.random.PRNGKey(0)


def _quad_batch(m=4, p=32, seed=0):
    A = jax.random.normal(jax.random.PRNGKey(seed), (m, p, p))
    Sigmas = jnp.einsum("tij,tkj->tik", A, A) / p
    cs = jax.random.normal(jax.random.PRNGKey(seed + 1), (m, p))
    return Sigmas, cs


def _reg_stats(m=4, p=32, seed=0):
    """Well-conditioned statistics (n > p regression data) where lasso
    solutions are O(1) — the right scale for 1e-5 comparisons."""
    data = gen_regression(jax.random.PRNGKey(seed), m=m, n=4 * p, p=p, s=5)
    return sufficient_stats(data.Xs, data.ys)


# ---------------------------------------------------------------------------
# historical per-task logistic implementations (the pre-engine-v2 code,
# kept here as the reference the batched path must reproduce)
# ---------------------------------------------------------------------------

def _old_logistic_lasso(X, y, lam, iters):
    n = X.shape[0]
    Sigma = (X.T @ X) / n
    L = 0.25 * power_iteration(Sigma)
    step = 1.0 / jnp.maximum(L, 1e-12)

    def grad(b):
        z = X @ b
        return -(X.T @ (y * jax.nn.sigmoid(-y * z))) / n

    prox = lambda v, s: soft_threshold(v, s * lam)
    return fista(grad, prox, jnp.zeros(X.shape[1], X.dtype), step, iters)


def _old_group_logistic(Xs, ys, lam, iters, prox_op):
    m, n, p = Xs.shape
    Sigmas, _ = sufficient_stats(Xs, ys)
    L = 0.25 / m * jnp.max(jax.vmap(power_iteration)(Sigmas))
    step = 1.0 / jnp.maximum(L, 1e-12)

    def grad(B):
        z = jnp.einsum("tnp,pt->tn", Xs, B)
        g = -jnp.einsum("tnp,tn->pt", Xs, ys * jax.nn.sigmoid(-ys * z)) / n
        return g / m

    prox = lambda V, s: prox_op(V, s * lam)
    return fista(grad, prox, jnp.zeros((p, m), Xs.dtype), step, iters)


def _old_refit_masked(X, y, support, steps):
    n, p = X.shape
    d = support.astype(X.dtype)
    Sigma = (X.T @ X) / n
    L = 0.25 * power_iteration(Sigma)
    step = 1.0 / jnp.maximum(L, 1e-12)

    def body(_, b):
        z = X @ b
        g = -(X.T @ (y * jax.nn.sigmoid(-y * z))) / n
        return (b - step * g) * d

    return jax.lax.fori_loop(0, steps, body, jnp.zeros(p, X.dtype))


# ---------------------------------------------------------------------------
# fused-momentum step: bitwise vs the historical two-op loop
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("iters", "interpret"))
def _two_op_loop(Sigmas, cs, lam, etas, iters, interpret=False):
    """The pre-v2 solve_lasso_batched body: one ista kernel step plus a
    separate jnp momentum pass per iteration."""
    C = cs[..., None]

    def step(Z):
        if interpret:
            return ista_step_batched(Sigmas, Z, C, etas, lam, block=32,
                                     interpret=True)
        return ista_step_batched_ref(Sigmas, Z, C, etas, lam)

    def body(_, carry):
        x, z, t = carry
        x_next = step(z)
        t_next = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        z_next = x_next + ((t - 1.0) / t_next) * (x_next - x)
        return x_next, z_next, t_next

    X0 = jnp.zeros_like(C)
    x, _, _ = jax.lax.fori_loop(0, iters, body,
                                (X0, X0, jnp.array(1.0, C.dtype)))
    return x[..., 0]


def test_fused_momentum_matches_two_op_bitwise_interpret():
    """Fused kernel (interpret mode) == historical kernel + jnp momentum."""
    Sigmas, cs = _quad_batch(m=2, p=32)
    etas = jnp.full((2,), 0.02)
    old = _two_op_loop(Sigmas, cs, 0.1, etas, 40, interpret=True)
    new = solve_lasso_batched(Sigmas, cs, 0.1, iters=40, etas=etas,
                              use_kernel=True, interpret=True, block=32)
    np.testing.assert_array_equal(np.asarray(old), np.asarray(new))


def test_fused_momentum_matches_two_op_bitwise_oracle():
    """Engine CPU fast path == historical ref-step + jnp momentum loop."""
    Sigmas, cs = _quad_batch(m=3, p=48)
    etas = jnp.full((3,), 0.02)
    old = _two_op_loop(Sigmas, cs, 0.2, etas, 60)
    new = solve_lasso_batched(Sigmas, cs, 0.2, iters=60, etas=etas)
    np.testing.assert_array_equal(np.asarray(old), np.asarray(new))


# ---------------------------------------------------------------------------
# in-place iterates: bitwise vs the two-output loop they replaced
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("iters", "check_every", "block"))
def _two_output_loop(Sigmas, C, lam, etas, X0, tol, iters, check_every,
                     block):
    """The lasso loop before in-place iterates: carry (x, z, t), one
    fused kernel step per iteration into fresh output buffers."""
    def body(_, carry):
        x, z, t = carry
        t_next = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        x_next, z_next = fista_step_batched_pallas(
            Sigmas, z, x, C, etas, lam, (t - 1.0) / t_next, bp=block[0],
            br=block[1], bk=block[2], interpret=True)
        return x_next, z_next, t_next

    def residual(x):
        x_fp = ista_step_batched_ref(Sigmas, x, C, etas, lam)
        return jnp.max(jnp.abs(x_fp - x))

    init = (X0, X0, jnp.array(1.0, C.dtype))
    if tol is None:
        x = jax.lax.fori_loop(0, iters, body, init)[0]
        return x, jnp.array(iters, jnp.int32)
    K = min(check_every, iters)

    def chunk(state):
        carry, it, _ = state
        end = jnp.minimum(it + K, iters)
        carry = jax.lax.fori_loop(it, end, body, carry)
        return carry, end, residual(carry[0])

    carry, n, _ = jax.lax.while_loop(
        lambda s: jnp.logical_and(s[1] < iters, s[2] > tol), chunk,
        (init, jnp.array(0, jnp.int32), jnp.array(jnp.inf, C.dtype)))
    return carry[0], n


def _copied_steps(n_iters, iters, tol, check_every):
    """Unpaired steps of the in-place loop, from its chunk lengths."""
    K = iters if tol is None else min(check_every, iters)
    return sum(min(K, n_iters - s) % 2 for s in range(0, n_iters, K))


INPLACE_CASES = pytest.mark.parametrize(
    "iters,tol,check_every,stops_early", [
        (40, None, 25, False),       # fixed budget, even
        (41, None, 25, False),       # fixed budget, odd: one unpaired step
        (60, 0.0, 10, False),        # check_every divides the ceiling
        (61, 0.0, 25, False),        # odd chunks, truncated last chunk
        (301, 1e-4, 25, True),       # odd chunks, tol stops it early
    ], ids=["even", "odd", "chunks-even", "chunks-odd", "tol-stops"])


@pytest.mark.parametrize("r", ["1", "p"])
@INPLACE_CASES
def test_inplace_iterates_match_two_output_loop_bitwise(
        r, iters, tol, check_every, stops_early):
    """The pair schedule (x' over x, z' alternating between z and a
    spare) gives bitwise the iterates and iteration counts of the
    two-output loop, for the lasso (r = 1) and the M solve (r = p), and
    `engine.fista_steps` counts its unpaired steps as copies."""
    _check_inplace_bitwise(r, iters, tol, check_every, stops_early,
                           p=32, block=lambda p, r: (8, r, p))


@pytest.mark.parametrize("r", ["1", "p"])
@INPLACE_CASES
def test_inplace_iterates_bitwise_at_decoupled_bk(
        r, iters, tol, check_every, stops_early):
    """The same, with the M solve's wide tiles: the whole output rows
    in one tile and the contraction streamed in 128-wide parts."""
    _check_inplace_bitwise(r, iters, tol, check_every, stops_early,
                           p=256, block=lambda p, r: (p, r, 128))


def _check_inplace_bitwise(r, iters, tol, check_every, stops_early, *, p,
                           block):
    from repro import obs
    from repro.core.engine import scaled_identity_m0
    Sigmas, cs = _reg_stats(m=2, p=p)
    m, p = cs.shape
    if r == "p":       # the M solve: identity RHS, scaled-identity start
        cs = jnp.broadcast_to(jnp.eye(p, dtype=cs.dtype), (m, p, p))
        beta0, lam = scaled_identity_m0(Sigmas), 0.1
    else:
        beta0, lam = 0.5 * cs, 0.05
    C = cs if cs.ndim == 3 else cs[..., None]
    X0 = beta0 if beta0.ndim == 3 else beta0[..., None]
    etas = 1.0 / jax.vmap(power_iteration)(Sigmas)
    block = block(p, C.shape[-1])
    old, n_old = _two_output_loop(Sigmas, C, lam, etas, X0, tol, iters,
                                  check_every, block)
    obs.reset()
    new, n_new = solve_lasso_batched(
        Sigmas, cs, lam, iters=iters, etas=etas, beta0=beta0,
        use_kernel=True, interpret=True, block=block, tol=tol,
        check_every=check_every, return_iters=True)
    n = int(n_new)
    assert n == int(n_old)
    assert (n < iters) == stops_early
    np.testing.assert_array_equal(np.asarray(new),
                                  np.asarray(old).reshape(new.shape))
    copied = _copied_steps(n, iters, tol, check_every)
    count = lambda carry: obs.counter_total(  # noqa: E731
        "engine.fista_steps", kind="lasso", carry=carry)
    assert (count("inplace"), count("copy")) == (n - copied, copied)
    obs.reset()


# ---------------------------------------------------------------------------
# convergence-aware early exit
# ---------------------------------------------------------------------------

def test_early_exit_matches_full_iteration_result():
    Sigmas, cs = _reg_stats(m=4, p=32)
    full, n_full = solve_lasso_batched(Sigmas, cs, 0.1, iters=1500,
                                       return_iters=True)
    early, n_early = solve_lasso_batched(Sigmas, cs, 0.1, iters=1500,
                                         tol=1e-7, check_every=50,
                                         return_iters=True)
    assert int(n_full) == 1500
    assert int(n_early) < 1500          # the while_loop actually stopped
    np.testing.assert_allclose(np.asarray(early), np.asarray(full),
                               atol=1e-5)


def test_early_exit_unreachable_tol_runs_full_budget():
    Sigmas, cs = _quad_batch(m=2, p=32)
    out, n = solve_lasso_batched(Sigmas, cs, 0.1, iters=100, tol=0.0,
                                 check_every=25, return_iters=True)
    assert int(n) == 100
    ref = solve_lasso_batched(Sigmas, cs, 0.1, iters=100)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_early_exit_iters_is_exact_ceiling():
    """iters not a multiple of check_every must NOT overshoot: the final
    chunk is truncated, so an unreachable tol reproduces the fixed-budget
    result bitwise."""
    Sigmas, cs = _quad_batch(m=2, p=32)
    out, n = solve_lasso_batched(Sigmas, cs, 0.1, iters=30, tol=0.0,
                                 check_every=25, return_iters=True)
    assert int(n) == 30
    ref = solve_lasso_batched(Sigmas, cs, 0.1, iters=30)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_logistic_early_exit_matches_full():
    data = gen_classification(KEY, m=3, n=100, p=32, s=4)
    full = solve_logistic_lasso_batched(data.Xs, data.ys, 0.05, iters=1200)
    early, n = solve_logistic_lasso_batched(data.Xs, data.ys, 0.05,
                                            iters=1200, tol=1e-7,
                                            check_every=50,
                                            return_iters=True)
    assert int(n) < 1200
    np.testing.assert_allclose(np.asarray(early), np.asarray(full),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# batched logistic solver path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m", [1, 3, 8])
def test_logistic_batched_matches_per_task_loop(m):
    data = gen_classification(jax.random.PRNGKey(m), m=m, n=90, p=40, s=4)
    lam = 0.05
    B = solve_logistic_lasso_batched(data.Xs, data.ys, lam, iters=250)
    B_ref = jax.vmap(lambda X, y: _old_logistic_lasso(X, y, lam, 250))(
        data.Xs, data.ys)
    np.testing.assert_allclose(np.asarray(B), np.asarray(B_ref), atol=1e-5)


def test_logistic_lasso_wrapper_matches_old_path():
    data = gen_classification(KEY, m=1, n=80, p=32, s=3)
    X, y = data.Xs[0], data.ys[0]
    b = logistic_lasso(X, y, 0.1, iters=200)
    b_ref = _old_logistic_lasso(X, y, 0.1, 200)
    np.testing.assert_allclose(np.asarray(b), np.asarray(b_ref), atol=1e-5)


def test_dsml_logistic_fit_matches_per_task_pipeline():
    """Steps 1-2 of the batched classification fit must reproduce the
    per-task lasso -> weighted-Hessian-debias pipeline they replaced."""
    from repro.core.debias import inverse_hessian_m
    data = gen_classification(KEY, m=3, n=100, p=32, s=4)
    lam, mu = 0.05, 0.1
    res = dsml_logistic_fit(data.Xs, data.ys, lam, mu, 0.5,
                            lasso_iters=200, debias_iters=200)
    bl_ref = jax.vmap(lambda X, y: _old_logistic_lasso(X, y, lam, 200))(
        data.Xs, data.ys)
    np.testing.assert_allclose(np.asarray(res.beta_local),
                               np.asarray(bl_ref), atol=1e-5)

    def old_debias(X, y, b):
        n = X.shape[0]
        z = X @ b
        w = jax.nn.sigmoid(z) * jax.nn.sigmoid(-z)
        Sw, _ = sufficient_stats(X[None], y[None], weights=w[None])
        M = inverse_hessian_m(Sw[0], mu, iters=200)
        score = (0.5 * (y + 1.0)) - jax.nn.sigmoid(z)
        return b + (M @ (X.T @ score)) / n

    bu_ref = jax.vmap(old_debias)(data.Xs, data.ys, bl_ref)
    np.testing.assert_allclose(np.asarray(res.beta_u), np.asarray(bu_ref),
                               atol=1e-4)


def test_group_and_icap_logistic_match_old_path():
    data = gen_classification(KEY, m=4, n=80, p=24, s=3)
    lam = 0.02
    Bg = group_logistic_lasso(data.Xs, data.ys, lam, iters=200)
    Bg_ref = _old_group_logistic(data.Xs, data.ys, lam, 200,
                                 group_soft_threshold)
    np.testing.assert_allclose(np.asarray(Bg), np.asarray(Bg_ref),
                               atol=1e-5)
    Bi = icap_logistic(data.Xs, data.ys, lam, iters=200)
    Bi_ref = _old_group_logistic(data.Xs, data.ys, lam, 200, prox_linf)
    np.testing.assert_allclose(np.asarray(Bi), np.asarray(Bi_ref),
                               atol=1e-5)


def test_refit_logistic_masked_matches_old_gd_loop():
    data = gen_classification(KEY, m=1, n=80, p=32, s=4)
    X, y = data.Xs[0], data.ys[0]
    sup = jnp.zeros(32, bool).at[:5].set(True)
    b = refit_logistic_masked(X, y, sup)
    b_ref = _old_refit_masked(X, y, sup, 200)
    np.testing.assert_allclose(np.asarray(b), np.asarray(b_ref), atol=1e-6)
    assert not np.any(np.asarray(b)[5:])      # mask respected


def test_logistic_warm_start_converges_faster():
    data = gen_classification(KEY, m=3, n=100, p=32, s=4)
    lam = 0.05
    B_star = solve_logistic_lasso_batched(data.Xs, data.ys, lam, iters=1500)
    _, n_cold = solve_logistic_lasso_batched(data.Xs, data.ys, lam,
                                             iters=1500, tol=1e-6,
                                             check_every=25,
                                             return_iters=True)
    _, n_warm = solve_logistic_lasso_batched(data.Xs, data.ys, lam,
                                             iters=1500, tol=1e-6,
                                             check_every=25, beta0=B_star,
                                             return_iters=True)
    assert int(n_warm) < int(n_cold)


# ---------------------------------------------------------------------------
# streaming logistic refit
# ---------------------------------------------------------------------------

def test_stream_refit_logistic_warm_generation():
    from repro.stream import init_stream_state, refit_logistic
    data = gen_classification(KEY, m=3, n=120, p=32, s=4)
    lam, mu, Lam = 0.05, 0.1, 0.1
    state0 = init_stream_state(3, 32)
    state1, info1 = refit_logistic(state0, data.Xs, data.ys, lam, mu, Lam,
                                   lasso_iters=400, debias_iters=400)
    assert int(info1.generation) == 1
    assert int(info1.support_size) > 0
    # warm second refit on the same window with a fraction of the budget
    # must land on (numerically) the same model
    state2, info2 = refit_logistic(state1, data.Xs, data.ys, lam, mu, Lam,
                                   lasso_iters=50, debias_iters=50,
                                   warm=True)
    assert int(info2.generation) == 2
    np.testing.assert_allclose(np.asarray(state2.beta_local),
                               np.asarray(state1.beta_local), atol=1e-4)
    assert float(info2.jaccard) == 1.0


# ---------------------------------------------------------------------------
# autotune
# ---------------------------------------------------------------------------

def test_autotune_cache_roundtrip(tmp_path, monkeypatch):
    from repro.kernels import autotune
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    autotune.clear_memory_cache()
    timed = []
    orig = autotune._time_candidate
    monkeypatch.setattr(autotune, "_time_candidate",
                        lambda fn, reps: (timed.append(1), orig(fn, reps))[1])
    blk = autotune.autotune_block(2, 32, 1, reps=1)
    assert blk in autotune.block_candidates(32, 1)
    assert len(timed) == len(autotune.block_candidates(32, 1))
    assert autotune.cache_path().exists()

    timed.clear()
    blk2 = autotune.autotune_block(2, 32, 1, reps=1)     # in-process hit
    assert blk2 == blk and not timed
    autotune.clear_memory_cache()                        # "new process"
    blk3 = autotune.autotune_block(2, 32, 1, reps=1)     # disk hit
    assert blk3 == blk and not timed


def test_autotune_keys_namespaced_per_kernel(tmp_path, monkeypatch):
    """ISSUE 4 fix: the three sweep families write per-kernel-namespaced
    keys, so coinciding dimension tuples (e.g. a (m, n, p) logistic key
    vs a (m, p, r) fista key with equal numbers) can never collide."""
    import json
    from repro.kernels import autotune
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    autotune.clear_memory_cache()
    autotune.autotune_block(2, 32, 16, reps=1)
    autotune.autotune_logistic_block(2, 32, 16, reps=1)
    autotune.autotune_rank_block(2, 32, 16, reps=1)
    disk = json.loads(autotune.cache_path().read_text())
    assert len(disk) == 3
    prefixes = sorted(k.split("/")[0] for k in disk)
    assert prefixes == ["fista_step", "logistic_grad", "rank_update"]


def test_autotune_migrates_legacy_unnamespaced_cache(tmp_path, monkeypatch):
    """Pre-namespace autotune.json files (fista-only, bare keys) stay
    readable: loads migrate them under fista_step/ and rewrite the file.
    The migrated entry names no candidate list, so it is not served:
    the shape is tuned anew under its list's key, beside the old one."""
    import json
    from repro.kernels import autotune
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    autotune.clear_memory_cache()
    autotune.cache_path().parent.mkdir(parents=True, exist_ok=True)
    autotune.cache_path().write_text(
        json.dumps({"cpu_m2_p32_r1_float32": [32, 1, 32]}))
    timed = []
    monkeypatch.setattr(autotune, "_time_candidate",
                        lambda fn, reps: timed.append(1) or 1.0)
    cands = autotune.block_candidates(32, 1)
    blk = autotune.autotune_block(2, 32, 1, reps=1)
    assert blk in cands and len(timed) == len(cands)
    tuned = autotune.cache_key("fista_step", "cpu",
                               {"m": 2, "p": 32, "r": 1}, jnp.float32,
                               cands)
    disk = json.loads(autotune.cache_path().read_text())
    assert disk == {"fista_step/cpu_m2_p32_r1_float32": [32, 1, 32],
                    tuned: list(blk)}


def test_autotune_migrates_legacy_logistic_int_values(tmp_path, monkeypatch):
    """ISSUE 5: pre-feature-tiling logistic winners were a bare int bn
    with an implicit full-lane bp = p. Loads widen them through the
    budgeted resolver ((n, p) read back off the key — full-lane here,
    where it fits; clamped to a servable tiling where it would not),
    rewrite the file once, and serve the migrated winner without
    re-timing."""
    import json
    from repro.kernels import autotune
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    autotune.clear_memory_cache()
    autotune.cache_path().parent.mkdir(parents=True, exist_ok=True)
    autotune.cache_path().write_text(
        json.dumps({"logistic_grad/cpu_m2_n32_p16_float32": 16}))
    monkeypatch.setattr(
        autotune, "_time_candidate",
        lambda fn, reps: (_ for _ in ()).throw(
            AssertionError("migrated key must be served, not re-timed")))
    assert autotune.autotune_logistic_block(2, 32, 16, reps=1) == (16, 16)
    disk = json.loads(autotune.cache_path().read_text())
    assert disk == {"logistic_grad/cpu_m2_n32_p16_float32": [16, 16]}


def test_autotune_logistic_never_sweeps_oracle_routed_shapes(tmp_path,
                                                             monkeypatch):
    """Shapes the dispatcher routes to the oracle return the budgeted
    default untimed — the cache is never polluted with unservable keys.
    Covers both routing clauses: sliver-degraded sample tiles
    (n = 1016 = 8*127) and p past the VMEM budget entirely (the padded
    gradient accumulator alone outgrows it around p ~ 16k)."""
    from repro.kernels import autotune
    from repro.kernels.logistic_grad.ops import (
        resolve_logistic_blocks, routes_to_oracle,
    )
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    autotune.clear_memory_cache()
    monkeypatch.setattr(
        autotune, "_time_candidate",
        lambda fn, reps: (_ for _ in ()).throw(
            AssertionError("oracle-routed shape must not sweep")))
    n_sliver = 8 * 127                      # 1016: sliver-degraded
    got = autotune.autotune_logistic_block(2, n_sliver, 64, reps=1)
    assert got == resolve_logistic_blocks(n_sliver, 64)
    p_huge = 20480                          # over-budget accumulator
    assert routes_to_oracle(32, p_huge)
    got_p = autotune.autotune_logistic_block(2, 32, p_huge, reps=1)
    assert got_p == resolve_logistic_blocks(32, p_huge)
    from repro.kernels.rank_update.ops import resolve_rank_blocks
    got_rank = autotune.autotune_rank_block(2, n_sliver, 64, reps=1)
    assert got_rank == resolve_rank_blocks(n_sliver, 64, 128)
    assert not autotune.cache_path().exists()


def test_explicit_block_bypasses_autotune(monkeypatch):
    from repro.kernels import autotune
    def boom(*a, **k):
        raise AssertionError("explicit block= must not consult autotune")
    monkeypatch.setattr(autotune, "autotune_block", boom)
    Sigmas, cs = _quad_batch(m=2, p=32)
    out = solve_lasso_batched(Sigmas, cs, 0.1, iters=20, use_kernel=True,
                              interpret=True, block=32)
    ref = solve_lasso_batched(Sigmas, cs, 0.1, iters=20)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_autotuned_default_policy_on_kernel_path(tmp_path, monkeypatch):
    """block=None on the kernel path LOOKS the autotuned winner up: on a
    cold cache it serves the deterministic default without sweeping
    (the policy runs under jit traces); once an eager sweep has filled
    the cache, the solve takes the winner."""
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    from repro.kernels import autotune
    autotune.clear_memory_cache()
    served = []
    orig = autotune.autotune_block
    monkeypatch.setattr(autotune, "autotune_block", lambda *a, **k: (
        served.append(orig(*a, **k)), served[-1])[1])
    Sigmas, cs = _reg_stats(m=2, p=32)
    ref = solve_lasso_batched(Sigmas, cs, 0.1, iters=30)
    out = solve_lasso_batched(Sigmas, cs, 0.1, iters=30, use_kernel=True,
                              interpret=True)       # block=None -> lookup
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    assert not autotune.cache_path().exists()       # a miss never sweeps
    winner = orig(2, 32, 1, reps=1)                 # the eager sweep
    assert autotune.cache_path().exists()
    out = solve_lasso_batched(Sigmas, cs, 0.1, iters=30, use_kernel=True,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    assert served[-1] == winner
