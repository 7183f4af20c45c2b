"""Pallas kernel tests: interpret-mode execution vs pure-jnp oracles,
sweeping shapes and dtypes per kernel (per the kernel contract)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention_op
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.group_threshold.ops import group_threshold
from repro.kernels.group_threshold.ref import group_threshold_ref
from repro.kernels.ista_step.ops import ista_solve, ista_step
from repro.kernels.ista_step.ref import ista_step_ref
from repro.kernels.logistic_grad.ops import logistic_grad, logistic_grad_unfused
from repro.kernels.logistic_grad.ref import logistic_grad_ref
from repro.kernels.rank_update.ops import rank_update, rank_update_unfused
from repro.kernels.rank_update.ref import rank_update_ref

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# ista_step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("p", [64, 128, 256, 384])
@pytest.mark.parametrize("r", [1, 8, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ista_step_shapes_dtypes(p, r, dtype):
    A = jax.random.normal(KEY, (p, p), jnp.float32)
    Sigma = (A @ A.T / p).astype(dtype)
    beta = jax.random.normal(jax.random.PRNGKey(1), (p, r), dtype)
    c = jax.random.normal(jax.random.PRNGKey(2), (p, r), dtype)
    out = ista_step(Sigma, beta, c, 0.05, 0.2)
    ref = ista_step_ref(Sigma, beta, c, 0.05, 0.2)
    tol = 1e-5 if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


def test_ista_step_vector_rhs():
    p = 128
    A = jax.random.normal(KEY, (p, p))
    Sigma = A @ A.T / p
    beta = jax.random.normal(jax.random.PRNGKey(1), (p,))
    c = jax.random.normal(jax.random.PRNGKey(2), (p,))
    out = ista_step(Sigma, beta, c, 0.05, 0.2)
    assert out.shape == (p,)
    ref = ista_step_ref(Sigma, beta[:, None], c[:, None], 0.05, 0.2)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ista_solve_matches_fista_solution():
    """The kernel-driven solver must satisfy the lasso KKT conditions."""
    p = 128
    A = jax.random.normal(KEY, (3 * p, p)) / jnp.sqrt(3.0 * p)
    Sigma = A.T @ A + 0.1 * jnp.eye(p)
    c = jax.random.normal(jax.random.PRNGKey(1), (p, 1)) * 0.3
    lam = 0.05
    beta = ista_solve(Sigma, c, lam, iters=1500)
    g = Sigma @ beta - c                      # subgradient condition
    assert float(jnp.max(jnp.abs(g))) <= lam * 1.05
    active = jnp.abs(beta) > 1e-6
    viol = jnp.where(active, jnp.abs(g + lam * jnp.sign(beta)), 0.0)
    assert float(jnp.max(viol)) < 5e-3


# ---------------------------------------------------------------------------
# logistic_grad (fused all-tasks gradient)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n,p,bn", [(1, 64, 32, 16), (3, 96, 48, 32),
                                      (4, 128, 200, 128), (2, 40, 16, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_logistic_grad_shapes_dtypes(m, n, p, bn, dtype):
    Xs = jax.random.normal(KEY, (m, n, p), dtype)
    ys = jnp.sign(jax.random.normal(jax.random.PRNGKey(1), (m, n))
                  ).astype(dtype)
    B = (jax.random.normal(jax.random.PRNGKey(2), (m, p)) * 0.3
         ).astype(dtype)
    out = logistic_grad(Xs, ys, B, block=bn, interpret=True)
    ref = logistic_grad_ref(Xs, ys, B)
    tol = 1e-5 if dtype == jnp.float32 else 0.05
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


def test_logistic_grad_unfused_matches_fused():
    m, n, p = 3, 64, 40
    Xs = jax.random.normal(KEY, (m, n, p))
    ys = jnp.sign(jax.random.normal(jax.random.PRNGKey(1), (m, n)))
    B = jax.random.normal(jax.random.PRNGKey(2), (m, p)) * 0.3
    fused = logistic_grad(Xs, ys, B, block=16, interpret=True)
    unfused = logistic_grad_unfused(Xs, ys, B, block=16, interpret=True)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(unfused),
                               atol=1e-6)


def test_logistic_grad_ragged_falls_back_to_oracle():
    """Ragged (n, p) must route to the oracle bitwise — callers never
    pre-check shapes."""
    m, n, p = 2, 33, 17
    Xs = jax.random.normal(KEY, (m, n, p))
    ys = jnp.sign(jax.random.normal(jax.random.PRNGKey(1), (m, n)))
    B = jax.random.normal(jax.random.PRNGKey(2), (m, p))
    out = logistic_grad(Xs, ys, B, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(logistic_grad_ref(Xs, ys, B)))


def _logistic_largep_case(m, n, p, seed=0, scale=0.02):
    Xs = jax.random.normal(jax.random.PRNGKey(seed), (m, n, p))
    ys = jnp.sign(jax.random.normal(jax.random.PRNGKey(seed + 1), (m, n)))
    B = jax.random.normal(jax.random.PRNGKey(seed + 2), (m, p)) * scale
    return Xs, ys, B


def test_logistic_grad_p8192_executes_on_kernel_path():
    """ISSUE 5 acceptance: p = 8192 (8-aligned n) is past the old
    MAX_FULL_LANE_P cliff but must now run the feature-tiled pallas
    kernel — the default policy picks a real feature tiling (bp < p)
    and matches the oracle to 1e-5."""
    from repro.kernels.logistic_grad.ops import (
        resolve_logistic_blocks, routes_to_oracle,
    )
    m, n, p = 2, 128, 8192
    assert not routes_to_oracle(n, p)
    bn, bp = resolve_logistic_blocks(n, p)
    assert bp < p and p % bp == 0           # genuinely feature-tiled
    Xs, ys, B = _logistic_largep_case(m, n, p)
    out = logistic_grad(Xs, ys, B, interpret=True)
    ref = logistic_grad_ref(Xs, ys, B)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("block", [(64, 1024), (32, 2048), (48, 1024),
                                   (100, 1500)])  # non-divisors included
def test_logistic_grad_p8192_explicit_tilings(block):
    m, n, p = 1, 192, 8192
    Xs, ys, B = _logistic_largep_case(m, n, p, seed=3)
    out = logistic_grad(Xs, ys, B, block=block, interpret=True)
    ref = logistic_grad_ref(Xs, ys, B)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_logistic_grad_unfused_feature_tiled_matches_fused():
    """The two-dispatch twin must tile features identically: same
    (bn, bp), bitwise-equal f32 accumulation order."""
    m, n, p = 2, 64, 8192
    Xs, ys, B = _logistic_largep_case(m, n, p, seed=5)
    fused = logistic_grad(Xs, ys, B, block=(32, 2048), interpret=True)
    unfused = logistic_grad_unfused(Xs, ys, B, block=(32, 2048),
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(unfused),
                               atol=1e-6)


@pytest.mark.parametrize("bad", [(16,), (8, 8, 8), "64", 12.5, (8.0, 8),
                                 True, (True, 8), 0, -8, (8, 0), (8, -8)])
def test_logistic_grad_block_validation_raises(bad):
    """The old dispatcher documented `block: int` but silently accepted
    any tuple via block[0]; malformed blocks must raise, never coerce."""
    Xs, ys, B = _logistic_largep_case(1, 16, 16)
    with pytest.raises(TypeError):
        logistic_grad(Xs, ys, B, block=bad, interpret=True)


def test_rank_and_ista_block_validation_raises():
    from repro.kernels.ista_step.ops import resolve_blocks
    Xs = jax.random.normal(KEY, (1, 16, 16))
    ys = jnp.sign(jax.random.normal(KEY, (1, 16)))
    with pytest.raises(TypeError):
        rank_update(Xs, ys, block=(8, 8, 8), interpret=True,
                    use_kernel=True)
    # validation must fire on the oracle path too (use_kernel False is
    # the CPU default) — a malformed block must never defer its crash
    # to the first TPU run
    with pytest.raises(TypeError):
        rank_update(Xs, ys, block=(8, 8, 8), use_kernel=False)
    with pytest.raises(TypeError):
        resolve_blocks(16, 1, (8, 8))       # a rank-style pair
    with pytest.raises(TypeError):
        resolve_blocks(16, 1, "128")
    # ragged shapes (which the oracle serves, ignoring blocks) and the
    # engine's CPU/oracle policies still validate
    from repro.kernels.ista_step.ops import ista_step_batched
    S33 = jax.random.normal(KEY, (1, 33, 33))
    b33 = jax.random.normal(KEY, (1, 33, 1))
    with pytest.raises(TypeError):
        ista_step_batched(S33, b33, b33, jnp.ones((1,)), 0.1, block=(8, 8))
    from repro.core.engine import (
        resolve_block_policy, resolve_logistic_block_policy,
    )
    with pytest.raises(TypeError):
        resolve_block_policy(1, 16, 1, jnp.float32, (8, 8), False)
    with pytest.raises(TypeError):
        resolve_logistic_block_policy(1, 16, 16, jnp.float32, (8, 8, 8),
                                      False)


def test_ista_resolve_blocks_no_sliver_halving():
    """The old local halving clip degraded non-divisor requests to
    single-element tiles (48-on-80 -> 1); the aligned divisor scan
    returns 40 for the sublane tile bp, and the lane tiles (bk, and br
    past r = 1) take a 128-multiple divisor or the whole axis — the
    only tiles the TPU compiler accepts there."""
    from repro.kernels.ista_step.ops import resolve_blocks
    assert resolve_blocks(80, 1, 48) == (40, 1, 80)
    assert resolve_blocks(384, 8, 128) == (128, 8, 128)
    assert resolve_blocks(200, 200, 128) == (40, 200, 200)
    assert resolve_blocks(1024, 1024, 64) == (64, 128, 128)


def test_sliver_shapes_route_to_oracle_bitwise():
    """ISSUE 5 regression: n = 1016 = 8*127 has no aligned divisor near
    the default 128 request (the divisor scan finds 127, which breaks
    sublane alignment; the best aligned tile is a sliver of 8). Both
    sample-streaming dispatchers must route it to the oracle instead of
    quietly running a 127-step sliver grid."""
    from repro.kernels.common import (
        aligned_fit_block, degrades_to_slivers, fit_block,
    )
    from repro.kernels.logistic_grad.ops import routes_to_oracle
    from repro.kernels.rank_update.ops import rank_routes_to_oracle
    assert fit_block(1016, 128) == 127      # unaligned: a trap, not a tile
    assert aligned_fit_block(1016, 128) == 8
    assert degrades_to_slivers(1016, 128)
    assert not degrades_to_slivers(80, 48)  # modest clip stays on-kernel
    assert not degrades_to_slivers(1016, 8)  # explicit tiny request honoured
    assert routes_to_oracle(1016, 64) and rank_routes_to_oracle(1016, 64)
    # p = 8168 = 8*1021 is past the full-lane budget and has no
    # 128-multiple divisor, so its only legal feature tile is the whole
    # axis, which busts the budget: it must route away
    from repro.kernels.logistic_grad.ops import resolve_logistic_blocks
    assert resolve_logistic_blocks(128, 8168)[1] == 8168
    assert routes_to_oracle(128, 8168)
    assert not routes_to_oracle(128, 8192)   # aligned divisors: on-kernel

    m, n, p = 2, 1016, 64
    Xs = jax.random.normal(KEY, (m, n, p))
    ys = jnp.sign(jax.random.normal(jax.random.PRNGKey(1), (m, n)))
    B = jax.random.normal(jax.random.PRNGKey(2), (m, p))
    out = logistic_grad(Xs, ys, B, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(logistic_grad_ref(Xs, ys, B)))
    S, c = rank_update(Xs, ys, interpret=True, use_kernel=True)
    S_ref, c_ref = rank_update_ref(Xs, ys)
    np.testing.assert_array_equal(np.asarray(S), np.asarray(S_ref))
    np.testing.assert_array_equal(np.asarray(c), np.asarray(c_ref))


# ---------------------------------------------------------------------------
# rank_update (fused rank-n sufficient-statistics update)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n,p,bp,bn", [(1, 64, 32, 16, 16),
                                         (3, 96, 48, 48, 32),
                                         (2, 128, 200, 128, 128),
                                         (4, 24, 16, 64, 64)])
@pytest.mark.parametrize("weighted", [False, True])
def test_rank_update_shapes_weights(m, n, p, bp, bn, weighted):
    Xs = jax.random.normal(KEY, (m, n, p))
    ys = jax.random.normal(jax.random.PRNGKey(1), (m, n))
    w = (jax.random.uniform(jax.random.PRNGKey(2), (m, n)) + 0.25
         ) if weighted else None
    S, c = rank_update(Xs, ys, w, block=(bp, bn), interpret=True,
                       use_kernel=True)
    S_ref, c_ref = rank_update_ref(Xs, ys, w)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(c), np.asarray(c_ref), atol=1e-5)


def test_rank_update_bf16():
    m, n, p = 2, 64, 32
    Xs = jax.random.normal(KEY, (m, n, p), jnp.bfloat16)
    ys = jax.random.normal(jax.random.PRNGKey(1), (m, n), jnp.bfloat16)
    S, c = rank_update(Xs, ys, block=32, interpret=True, use_kernel=True)
    S_ref, c_ref = rank_update_ref(Xs, ys)
    np.testing.assert_allclose(np.asarray(S, np.float32),
                               np.asarray(S_ref, np.float32), atol=0.05)
    np.testing.assert_allclose(np.asarray(c, np.float32),
                               np.asarray(c_ref, np.float32), atol=0.05)


def test_rank_update_unfused_matches_fused():
    m, n, p = 3, 48, 32
    Xs = jax.random.normal(KEY, (m, n, p))
    ys = jax.random.normal(jax.random.PRNGKey(1), (m, n))
    w = jax.random.uniform(jax.random.PRNGKey(2), (m, n)) + 0.25
    S_f, c_f = rank_update(Xs, ys, w, block=16, interpret=True,
                           use_kernel=True)
    S_u, c_u = rank_update_unfused(Xs, ys, w, block=16, interpret=True)
    np.testing.assert_allclose(np.asarray(S_f), np.asarray(S_u), atol=1e-6)
    np.testing.assert_allclose(np.asarray(c_f), np.asarray(c_u), atol=1e-6)


def test_rank_update_ragged_falls_back_to_oracle():
    m, n, p = 2, 33, 17
    Xs = jax.random.normal(KEY, (m, n, p))
    ys = jax.random.normal(jax.random.PRNGKey(1), (m, n))
    S, c = rank_update(Xs, ys, interpret=True, use_kernel=True)
    S_ref, c_ref = rank_update_ref(Xs, ys)
    np.testing.assert_array_equal(np.asarray(S), np.asarray(S_ref))
    np.testing.assert_array_equal(np.asarray(c), np.asarray(c_ref))


def test_sufficient_stats_kernel_path_matches_default():
    """The engine entry point itself: kernel routing must be invisible
    to callers of `sufficient_stats`."""
    from repro.core.engine import sufficient_stats
    Xs = jax.random.normal(KEY, (3, 64, 48))
    ys = jax.random.normal(jax.random.PRNGKey(1), (3, 64))
    S0, c0 = sufficient_stats(Xs, ys)
    S1, c1 = sufficient_stats(Xs, ys, use_kernel=True, interpret=True,
                              block=32)
    np.testing.assert_allclose(np.asarray(S0), np.asarray(S1), atol=1e-5)
    np.testing.assert_allclose(np.asarray(c0), np.asarray(c1), atol=1e-5)


# ---------------------------------------------------------------------------
# group_threshold
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("p,m", [(64, 4), (256, 10), (1024, 16), (200, 10)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_group_threshold_shapes_dtypes(p, m, dtype):
    B = jax.random.normal(KEY, (p, m), dtype) * 2.0
    out, keep = group_threshold(B, 2.0)
    ref_out, ref_keep = group_threshold_ref(B, 2.0)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref_out, np.float32), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(keep), np.asarray(ref_keep))


def test_group_threshold_edge_lambdas():
    B = jax.random.normal(KEY, (128, 8))
    out0, keep0 = group_threshold(B, 0.0)
    assert bool(jnp.all(keep0))                     # every row has norm > 0
    outinf, keepinf = group_threshold(B, 1e9)
    assert not bool(jnp.any(keepinf))
    assert bool(jnp.all(outinf == 0))


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,n,k,h", [(128, 4, 4, 32), (256, 8, 2, 64),
                                     (64, 2, 1, 128), (192, 4, 2, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_kernel_shapes_dtypes(s, n, k, h, dtype):
    q = jax.random.normal(KEY, (2, s, n, h), dtype)
    kk = jax.random.normal(jax.random.PRNGKey(1), (2, s, k, h), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (2, s, k, h), dtype)
    out = flash_attention_op(q, kk, v, causal=True, bq=64, bk=64)
    ref = flash_attention_ref(q.astype(jnp.float32), kk.astype(jnp.float32),
                              v.astype(jnp.float32), causal=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [16, 64])
def test_flash_kernel_sliding_window(window):
    s = 256
    q = jax.random.normal(KEY, (1, s, 4, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, s, 4, 32))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, s, 4, 32))
    out = flash_attention_op(q, k, v, causal=True, window=window,
                             bq=64, bk=64)
    ref = flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_kernel_noncausal():
    s = 128
    q = jax.random.normal(KEY, (1, s, 2, 64))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, s, 2, 64))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, s, 2, 64))
    out = flash_attention_op(q, k, v, causal=False, bq=32, bk=32)
    ref = flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
