"""Jit'd public wrapper for the fused ISTA step.

On CPU (this container) the kernel body executes in interpret mode; on a
real TPU the same BlockSpecs compile to Mosaic. `ista_solve` runs a whole
FISTA-free proximal-gradient loop with the fused kernel as the body —
the drop-in accelerated path for core/solvers.lasso and
core/debias.inverse_hessian_m.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import (
    aligned_fit_block, lane_fit_block, record_route, validate_block,
)
from repro.kernels.common import on_tpu as _on_tpu
from repro.kernels.ista_step.kernel import (
    MIB, fista_step_batched_inplace_pallas, ista_step_batched_pallas,
    ista_step_pallas, step_vmem_bytes, step_vmem_limit,
)
from repro.kernels.ista_step.ref import (
    fista_step_batched_ref, ista_step_batched_ref, ista_step_ref,
)


def is_ragged(p: int, r: int) -> bool:
    """The shape half of the kernel routing predicate: shapes the
    pallas tiling cannot legally cover go to the jnp oracle (which
    ignores blocks). Shared by the step dispatchers below and the
    engine's block policy so the two can never desync."""
    return bool(p % 8 or (r % 8 and r != 1))


# the most VMEM one grid step may take by `step_vmem_bytes`: a v5e core
# holds 128 MiB, and the call asks for this plus `VMEM_HEADROOM`
STEP_VMEM_BUDGET = 96 * MIB


def resolve_blocks(p: int, r: int, block) -> tuple:
    """Normalize a block policy to concrete (bp, br, bk) tile sizes.

    `block` is either one int (square bp = bk tiles, the historical
    policy) or an explicit (bp, br, bk) triple, e.g. an autotuned winner
    from `repro.kernels.autotune`. Each entry is fitted to the TPU's
    (8, 128) tiling: bp, the sublane axis of every tile, to the largest
    8-aligned divisor of p; bk and br, which land on lanes, to the
    largest 128-multiple divisor of their axis or the whole axis (so
    the paper's p = 200 takes (40, 1, 200), never a (40, 40) block the
    compiler refuses). Anything else raises — a wrong-arity tuple (e.g.
    a (bp, bn) rank pair) must not be silently unpacked into the wrong
    axes.
    """
    bp, br, bk = validate_block(block, 3, "(bp, br, bk)")
    return (aligned_fit_block(p, bp), lane_fit_block(r, br),
            lane_fit_block(p, bk))


def step_route_reason(p: int, r: int, block=128):
    """Routing verdict plus its telemetry label: None on the kernel
    path, else `ragged` (an axis the tiling cannot cover) or
    `vmem_budget` (the legal tiles — whole-axis lane tiles for p or r
    with no 128-multiple divisor — outgrow `STEP_VMEM_BUDGET`)."""
    bp, br, bk = resolve_blocks(p, r, block)
    if is_ragged(p, r):
        return "ragged"
    if step_vmem_bytes(bp, br, bk) > STEP_VMEM_BUDGET:
        return "vmem_budget"
    return None


def step_routes_to_oracle(p: int, r: int, block=128) -> bool:
    """Routing predicate shared with the engine's block policy."""
    return step_route_reason(p, r, block) is not None


def ista_step_batched(Sigmas, betas, cs, etas, lam, *, block: int = 128,
                      interpret: bool | None = None):
    """One fused ISTA step for m tasks. Sigmas (m, p, p); betas, cs
    (m, p) or (m, p, r); etas (m,) per-task step sizes; lam scalar or
    per-task (m,).

    Routes to the batched pallas kernel on MXU-friendly shapes (ragged
    shapes fall back to the batched jnp oracle); `interpret` defaults to
    True off-TPU so the same BlockSpecs execute everywhere.
    """
    squeeze = betas.ndim == 2
    if squeeze:
        betas = betas[..., None]
        cs = cs[..., None]
    m, p, r = betas.shape
    # resolve (and so validate) blocks before the ragged short-circuit:
    # a malformed block must raise on every path
    bp, br, bk = resolve_blocks(p, r, block)
    interp = (not _on_tpu()) if interpret is None else interpret
    reason = step_route_reason(p, r, block)
    record_route("ista_step_batched", reason, blocks=(bp, br, bk))
    if reason is not None:
        out = ista_step_batched_ref(Sigmas, betas, cs, etas, lam)
    else:
        out = ista_step_batched_pallas(Sigmas, betas, cs, etas, lam,
                                       bp=bp, br=br, bk=bk, interpret=interp)
    return out[..., 0] if squeeze else out


def fista_step_batched(Sigmas, zs, xs, ws, cs, etas, lam, theta, *,
                       block=128, interpret: bool | None = None):
    """One fused FISTA iteration (prox step + momentum extrapolation)
    for m tasks. Sigmas (m, p, p); zs/xs/ws/cs (m, p) or (m, p, r);
    etas (m,); lam scalar or per-task (m,); theta the scalar momentum
    coefficient. Returns (x_next, z_next).

    The kernel writes in place (`fista_step_batched_inplace_pallas`):
    x_next into `xs`'s buffer and z_next into the spare `ws`, whose
    contents are never read; the oracle ignores `ws`. Same routing
    policy as `ista_step_batched`: pallas on MXU-friendly shapes
    (`block` is an int or an autotuned (bp, br, bk) triple),
    batched-jnp oracle on ragged shapes, interpret mode off-TPU.
    """
    squeeze = zs.ndim == 2
    if squeeze:
        zs, xs, ws, cs = (a[..., None] for a in (zs, xs, ws, cs))
    m, p, r = zs.shape
    bp, br, bk = resolve_blocks(p, r, block)    # validate on every path
    interp = (not _on_tpu()) if interpret is None else interpret
    reason = step_route_reason(p, r, block)
    limit = step_vmem_limit(bp, br, bk)
    record_route("fista_step_batched", reason, blocks=(bp, br, bk),
                 vmem_mib=None if reason is not None
                 else "default" if limit is None else limit // MIB)
    if reason is not None:
        xn, zn = fista_step_batched_ref(Sigmas, zs, xs, cs, etas, lam, theta)
    else:
        xn, zn = fista_step_batched_inplace_pallas(
            Sigmas, zs, xs, ws, cs, etas, lam, theta, bp=bp, br=br, bk=bk,
            interpret=interp)
    return (xn[..., 0], zn[..., 0]) if squeeze else (xn, zn)


def ista_step(Sigma, beta, c, eta, lam, *, block: int = 128,
              interpret: bool | None = None):
    """One fused ISTA step. Shapes: Sigma (p,p); beta, c (p,) or (p,r)."""
    squeeze = beta.ndim == 1
    if squeeze:
        beta = beta[:, None]
        c = c[:, None]
    p, r = beta.shape
    bp, br, bk = resolve_blocks(p, r, block)    # validate on every path
    interp = (not _on_tpu()) if interpret is None else interpret
    reason = step_route_reason(p, r, block)
    record_route("ista_step", reason, blocks=(bp, br, bk))
    if reason is not None:
        out = ista_step_ref(Sigma, beta, c, eta, lam)   # ragged fallback
    else:
        out = ista_step_pallas(Sigma, beta, c, eta, lam, bp=bp, br=br,
                               bk=bk, interpret=interp)
    return out[:, 0] if squeeze else out


@functools.partial(jax.jit, static_argnames=("iters", "block", "interpret"))
def ista_solve(Sigma, c, lam, *, iters: int = 400, block: int = 128,
               interpret: bool | None = None):
    """Proximal-gradient lasso solve on sufficient statistics via the
    fused kernel: min_b 1/2 b'Sigma b - c'b + lam|b|_1 (multi-RHS)."""
    from repro.core.solvers import power_iteration
    eta = 1.0 / jnp.maximum(power_iteration(Sigma), 1e-12)
    beta0 = jnp.zeros_like(c)

    def body(_, beta):
        return ista_step(Sigma, beta, c, eta, lam, block=block,
                         interpret=interpret)

    return jax.lax.fori_loop(0, iters, body, beta0)
