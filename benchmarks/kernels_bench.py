"""Kernel microbenchmarks.

On this CPU container, interpret-mode timings measure the Python
emulation (NOT TPU perf) — reported for completeness; `derived` carries
the analytic FLOPs per call, which is the number the TPU roofline uses.
The jnp reference path is timed as the XLA-CPU baseline.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.paper_common import time_fn as _time
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.group_threshold.ref import group_threshold_ref
from repro.kernels.ista_step.ops import (
    fista_step_batched, ista_step, ista_step_batched,
)
from repro.kernels.ista_step.ref import ista_step_batched_ref, ista_step_ref
from repro.kernels.logistic_grad.ops import logistic_grad, logistic_grad_unfused
from repro.kernels.logistic_grad.ref import logistic_grad_ref
from repro.kernels.rank_update.ops import rank_update, rank_update_unfused
from repro.kernels.rank_update.ref import rank_update_ref


def _interleaved_pair(fa, fb, *args, reps: int = 2, rounds: int = 5):
    """Drift-robust pairing: interpret-mode emulation speed drifts
    within a process, so interleave the two paths (the original
    min-of-2 pattern, widened to `rounds`) and report min-time per path
    plus the MEDIAN of the per-round a-vs-b ratios — adjacent
    measurements see the same drift, so the paired ratio cancels it
    where a ratio of independent minima does not."""
    ta, tb = [], []
    for _ in range(rounds):
        ta.append(_time(fa, *args, reps=reps))
        tb.append(_time(fb, *args, reps=reps))
    ratios = sorted(b / a for a, b in zip(ta, tb))
    return min(ta), min(tb), ratios[len(ratios) // 2]


def main():
    rows = []
    key = jax.random.PRNGKey(0)

    # ista_step: p=512, r=512 (the M-matrix solve shape for p=512)
    p = r = 512
    A = jax.random.normal(key, (p, p))
    Sigma = A @ A.T / p
    beta = jax.random.normal(key, (p, r))
    c = jax.random.normal(key, (p, r))
    f = jax.jit(lambda S, b, cc: ista_step_ref(S, b, cc, 0.01, 0.1))
    us = _time(f, Sigma, beta, c)
    flops = 2 * p * p * r
    rows.append(f"kernel_ista_step_p{p}_r{r},{us:.0f},flops={flops}")

    # batched lasso hot step (m=16 tasks, p=512): the engine's fused
    # multi-RHS pallas step vs the per-task vmap path, both in interpret
    # mode (the TPU BlockSpecs executed on CPU), plus the XLA batched
    # oracle that the engine uses as its CPU fast path.
    m = 16
    p = 512
    A = jax.random.normal(key, (m, p, p))
    Sigmas = jnp.einsum("tij,tkj->tik", A, A) / p
    B = jax.random.normal(jax.random.PRNGKey(1), (m, p, 1))
    C = jax.random.normal(jax.random.PRNGKey(2), (m, p, 1))
    etas = jnp.full((m,), 0.01)
    flops = 2 * m * p * p
    fused = jax.jit(lambda S, b, c: ista_step_batched(S, b, c, etas, 0.1,
                                                      interpret=True))
    vmapped = jax.jit(jax.vmap(
        lambda S, b, c: ista_step(S, b, c, 0.01, 0.1, interpret=True)))
    oracle = jax.jit(lambda S, b, c: ista_step_batched_ref(S, b, c, etas, 0.1))
    us_fused, us_vmap, r_fv = _interleaved_pair(fused, vmapped, Sigmas, B, C,
                                                rounds=7)
    us_ref = _time(oracle, Sigmas, B, C)
    rows.append(f"kernel_ista_batched_fused_m16_p512,{us_fused:.0f},flops={flops}")
    rows.append(f"kernel_ista_batched_vmap_m16_p512,{us_vmap:.0f},flops={flops}")
    rows.append(f"kernel_ista_batched_xla_ref_m16_p512,{us_ref:.0f},flops={flops}")
    rows.append(f"kernel_ista_batched_fused_over_vmap,{us_fused:.0f},"
                f"speedup={r_fv:.2f}x")

    # one full FISTA iteration (engine v2): the fused-momentum kernel
    # (prox + extrapolation in one dispatch) vs the historical two-op
    # path (ista kernel + separate jnp momentum pass), interpret mode
    X = jax.random.normal(jax.random.PRNGKey(4), (m, p, 1))
    theta = 0.6
    fista_fused = jax.jit(lambda S, z, x, c: fista_step_batched(
        S, z, x, jnp.zeros_like(z), c, etas, 0.1, theta, interpret=True))

    def _two_op(S, z, x, c):
        xn = ista_step_batched(S, z, c, etas, 0.1, interpret=True)
        return xn, xn + theta * (xn - x)
    two_op = jax.jit(_two_op)
    us_f, us_2, r_f2 = _interleaved_pair(fista_fused, two_op, Sigmas, B, X, C)
    rows.append(f"kernel_fista_fused_m16_p512,{us_f:.0f},flops={flops}")
    rows.append(f"kernel_fista_two_op_m16_p512,{us_2:.0f},flops={flops}")
    rows.append(f"kernel_fista_fused_over_two_op,{us_f:.0f},"
                f"speedup={r_f2:.2f}x")

    # batched logistic solve (engine v2): one all-tasks einsum FISTA
    # loop vs the per-task vmap(fista) path it replaced (m=16, p=512)
    from repro.core.engine import solve_logistic_lasso_batched
    from repro.core.prox import soft_threshold
    from repro.core.solvers import fista, power_iteration
    n_log, iters_log = 128, 30
    Xs = jax.random.normal(jax.random.PRNGKey(5), (m, n_log, p))
    ys = jnp.sign(jax.random.normal(jax.random.PRNGKey(6), (m, n_log)))

    def _per_task(X, y):
        Sg = (X.T @ X) / n_log
        step = 1.0 / jnp.maximum(0.25 * power_iteration(Sg), 1e-12)

        def grad(b):
            z = X @ b
            return -(X.T @ (y * jax.nn.sigmoid(-y * z))) / n_log

        prox = lambda v, s: soft_threshold(v, s * 0.05)
        return fista(grad, prox, jnp.zeros(p, X.dtype), step, iters_log)

    batched = jax.jit(lambda X, y: solve_logistic_lasso_batched(
        X, y, 0.05, iters=iters_log))
    vmap_log = jax.jit(jax.vmap(_per_task))
    us_b, us_v, r_bv = _interleaved_pair(batched, vmap_log, Xs, ys)
    flops_log = 4 * m * n_log * p * iters_log       # fwd + bwd einsum per iter
    rows.append(f"logistic_solve_batched_m16_p512,{us_b:.0f},flops={flops_log}")
    rows.append(f"logistic_solve_vmap_m16_p512,{us_v:.0f},flops={flops_log}")
    rows.append(f"logistic_solve_batched_over_vmap,{us_b:.0f},"
                f"speedup={r_bv:.2f}x")

    # fused logistic-gradient kernel (engine hot path for every
    # Section-4 solve): one dispatch computing X@b, the sigmoid
    # residual, and the X'r back-projection from the same resident
    # tiles, vs the unfused two-dispatch pallas pair (forward matvec
    # kernel + jnp residual + back-projection kernel), both interpret
    # mode; the XLA einsum oracle (the engine's CPU fast path) for
    # context
    n_g = 128
    Xg = jax.random.normal(jax.random.PRNGKey(7), (m, n_g, p))
    yg = jnp.sign(jax.random.normal(jax.random.PRNGKey(8), (m, n_g)))
    Bg = jax.random.normal(jax.random.PRNGKey(9), (m, p)) * 0.1
    g_fused = jax.jit(lambda X, y, b: logistic_grad(X, y, b, interpret=True))
    g_unfused = jax.jit(lambda X, y, b: logistic_grad_unfused(
        X, y, b, interpret=True))
    g_ref = jax.jit(logistic_grad_ref)
    us_gf, us_gu, r_gu = _interleaved_pair(g_fused, g_unfused, Xg, yg, Bg)
    us_gr = _time(g_ref, Xg, yg, Bg)
    flops_g = 4 * m * n_g * p          # fwd + bwd matvec
    rows.append(f"logistic_grad_fused_m16_p512,{us_gf:.0f},flops={flops_g}")
    rows.append(f"logistic_grad_unfused_m16_p512,{us_gu:.0f},flops={flops_g}")
    rows.append(f"logistic_grad_xla_ref_m16_p512,{us_gr:.0f},flops={flops_g}")
    rows.append(f"logistic_grad_fused_over_unfused,{us_gf:.0f},"
                f"speedup={r_gu:.2f}x")

    # feature-tiled large-p slab (DESIGN.md §12): p = 8192 is past the
    # old full-lane cliff that routed every large-p gradient to the
    # oracle; the two-phase fused sweep vs the unfused pair at the same
    # budgeted (bn, bp) tiling, XLA einsum oracle for context
    from repro.kernels.logistic_grad.ops import (
        resolve_logistic_blocks, routes_to_oracle,
    )
    m_l, n_l, p_l = 4, 128, 8192
    assert not routes_to_oracle(n_l, p_l), "large-p must stay on-kernel"
    bn_l, bp_l = resolve_logistic_blocks(n_l, p_l)
    Xl = jax.random.normal(jax.random.PRNGKey(12), (m_l, n_l, p_l))
    yl = jnp.sign(jax.random.normal(jax.random.PRNGKey(13), (m_l, n_l)))
    Bl = jax.random.normal(jax.random.PRNGKey(14), (m_l, p_l)) * 0.02
    # g_fused/g_unfused/g_ref from the p=512 pair are shape-generic
    us_lf, us_lu, r_lu = _interleaved_pair(g_fused, g_unfused, Xl, yl, Bl)
    us_lr = _time(g_ref, Xl, yl, Bl, reps=3)
    flops_l = 4 * m_l * n_l * p_l
    rows.append(f"logistic_grad_fused_m4_n128_p8192,{us_lf:.0f},"
                f"flops={flops_l},bn={bn_l},bp={bp_l}")
    rows.append(f"logistic_grad_unfused_m4_n128_p8192,{us_lu:.0f},"
                f"flops={flops_l}")
    rows.append(f"logistic_grad_xla_ref_m4_n128_p8192,{us_lr:.0f},"
                f"flops={flops_l}")
    rows.append(f"logistic_grad_fused_over_unfused_p8192,{us_lf:.0f},"
                f"speedup={r_lu:.2f}x")

    # fused rank-n statistics update (streaming ingest hot path): Sigma
    # and c from ONE pass over the sample chunk vs the unfused
    # two-dispatch pair (covariance kernel + correlation kernel, X
    # streamed twice), interpret mode; XLA einsum oracle for context
    m_r, n_r, p_r = 8, 512, 256
    Xr = jax.random.normal(jax.random.PRNGKey(10), (m_r, n_r, p_r))
    yr = jax.random.normal(jax.random.PRNGKey(11), (m_r, n_r))
    r_fused = jax.jit(lambda X, y: rank_update(X, y, interpret=True,
                                               use_kernel=True))
    r_unfused = jax.jit(lambda X, y: rank_update_unfused(X, y,
                                                         interpret=True))
    r_ref = jax.jit(lambda X, y: rank_update_ref(X, y))
    us_rf, us_ru, r_ru = _interleaved_pair(r_fused, r_unfused, Xr, yr)
    us_rr = _time(r_ref, Xr, yr)
    flops_r = 2 * m_r * n_r * p_r * (p_r + 1)
    rows.append(f"rank_update_fused_m8_n512_p256,{us_rf:.0f},flops={flops_r}")
    rows.append(f"rank_update_unfused_m8_n512_p256,{us_ru:.0f},"
                f"flops={flops_r}")
    rows.append(f"rank_update_xla_ref_m8_n512_p256,{us_rr:.0f},"
                f"flops={flops_r}")
    rows.append(f"rank_update_fused_over_unfused,{us_rf:.0f},"
                f"speedup={r_ru:.2f}x")

    # streaming ingest: the always-on rank-n update of the stream layer
    # (one chunk of m=16 tasks x n=1024 rows into p=256 running stats)
    from repro.stream import ingest, init_stream_state
    m, n, p = 16, 1024, 256
    state = init_stream_state(m, p)
    Xb = jax.random.normal(key, (m, n, p))
    yb = jax.random.normal(jax.random.PRNGKey(3), (m, n))
    us = _time(ingest, state, Xb, yb)
    flops = 2 * m * n * p * p
    rows.append(f"stream_ingest_m{m}_n{n}_p{p},{us:.0f},flops={flops},"
                f"rows_per_s={m * n / (us * 1e-6):.0f}")

    # group_threshold: p=200000 rows x m=16
    B = jax.random.normal(key, (200_000, 16))
    f = jax.jit(lambda b: group_threshold_ref(b, 2.0))
    us = _time(f, B)
    rows.append(f"kernel_group_threshold_200k_x16,{us:.0f},bytes={B.size * 4}")

    # flash attention fwd: S=2048, 8 heads, H=64
    q = jax.random.normal(key, (1, 2048, 8, 64), jnp.float32)
    k = jax.random.normal(key, (1, 2048, 8, 64), jnp.float32)
    v = jax.random.normal(key, (1, 2048, 8, 64), jnp.float32)
    f = jax.jit(lambda q, k, v: flash_attention_ref(q, k, v, causal=True))
    us = _time(f, q, k, v, reps=5)
    flops = 4 * 2048 * 2048 * 8 * 64  # qk + pv
    rows.append(f"kernel_flash_attn_s2048_h8,{us:.0f},flops={flops}")
    return rows


if __name__ == "__main__":
    for r in main():
        print(r)
