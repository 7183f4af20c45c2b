"""Pallas TPU kernel: fused ISTA step (matmul + gradient step + prox).

Tiling: the output (p, r) is tiled (BP, BR); the contraction over p runs
as the innermost grid dimension with a VMEM f32 scratch accumulator —
each (i, j) output tile accumulates Sigma[i, :] @ beta[:, j] over k-tiles
on the MXU, then the epilogue (gradient step + soft threshold, VPU ops)
fires on the last k step. Tiles default to 128 (MXU-aligned); the scalars
(eta, lam) ride in SMEM.

`ista_step_batched_pallas` extends the same tiling with a leading task
grid dimension: all m per-task solves of the DSML hot loop run as one
pallas call over per-task Sigma tiles and per-task step sizes (SMEM).

`fista_step_batched_pallas` is the engine-v2 variant: the epilogue also
applies the FISTA momentum extrapolation, emitting BOTH the prox'd
iterate `x_next` and the look-ahead point `z_next = x_next +
theta (x_next - x_prev)` from the same VMEM tiles — one kernel dispatch
and one HBM round trip per FISTA iteration where the two-op path paid a
kernel plus a separate jnp momentum pass over (m, p, r). The momentum
coefficient `theta` rides in SMEM next to `etas`/`lam`.
`fista_step_batched_inplace_pallas` is the same call with its outputs
aliased onto buffers the engine's loop owns (x_next over x_prev, z_next
over a spare stack), so the loop's carry is never copied.

Each call asks Mosaic for the scoped VMEM its tiles take
(`step_vmem_limit`) where that is more than the default limit, so the
r = p step can take output tiles wide enough that Sigma and z are read
from HBM once or twice per step instead of p / br and p / bp times.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import LANE

MIB = 1024 * 1024
# Mosaic's scoped VMEM limit for a call that asks for none
DEFAULT_SCOPED_VMEM = 16 * MIB
# asked for beyond the byte model, so a call never sits at its edge
VMEM_HEADROOM = 4 * MIB


def step_vmem_bytes(bp: int, br: int, bk: int) -> int:
    """VMEM one grid step of the step kernels allocates, at f32 with
    lanes padded to full 128-lane register tiles: the (bp, bk) Sigma
    tile and the (bk, br) contraction tile, double-buffered, and
    fourteen (bp, br) tiles: the five iterate/x_prev/c/output windows
    double-buffered, the accumulator, and three epilogue temporaries.
    Exact for the fused FISTA step: compiles for a described v5e
    report this scoped allocation at every tiling tried, from (256,
    512, 256) to (1024, 2048, 256); the ISTA steps hold fewer
    windows."""
    pad = lambda d: -(-d // LANE) * LANE
    return 8 * bp * pad(bk) + 8 * bk * pad(br) + 56 * bp * pad(br)


def step_vmem_limit(bp: int, br: int, bk: int) -> int | None:
    """The scoped VMEM limit a step call with these tiles asks for, in
    bytes (whole MiB): its byte model plus `VMEM_HEADROOM`, or None
    where the default limit already holds that."""
    need = -(-(step_vmem_bytes(bp, br, bk) + VMEM_HEADROOM) // MIB) * MIB
    return need if need > DEFAULT_SCOPED_VMEM else None


def _compiler_params(bp: int, br: int, bk: int):
    limit = step_vmem_limit(bp, br, bk)
    return None if limit is None else pltpu.CompilerParams(
        vmem_limit_bytes=limit)


def _ista_kernel(eta_lam_ref, sig_ref, beta_ref, beta_tile_ref, c_ref,
                 out_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(sig_ref[...], beta_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        eta = eta_lam_ref[0]
        lam = eta_lam_ref[1]
        grad = acc_ref[...] - c_ref[...].astype(jnp.float32)
        z = beta_tile_ref[...].astype(jnp.float32) - eta * grad
        tau = eta * lam
        out = jnp.sign(z) * jnp.maximum(jnp.abs(z) - tau, 0.0)
        out_ref[...] = out.astype(out_ref.dtype)


def _ista_batched_kernel(eta_lam_ref, sig_ref, beta_ref, beta_tile_ref,
                         c_ref, out_ref, acc_ref, *, nk: int, m: int):
    t = pl.program_id(0)
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(sig_ref[0], beta_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        eta = eta_lam_ref[t]            # per-task step size
        lam = eta_lam_ref[m + t]        # per-task regularization weight
        grad = acc_ref[...] - c_ref[0].astype(jnp.float32)
        z = beta_tile_ref[0].astype(jnp.float32) - eta * grad
        tau = eta * lam
        out = jnp.sign(z) * jnp.maximum(jnp.abs(z) - tau, 0.0)
        out_ref[0] = out.astype(out_ref.dtype)


def _fista_batched_kernel(scal_ref, sig_ref, z_ref, z_tile_ref, x_ref,
                          c_ref, xn_ref, zn_ref, acc_ref, *, nk: int,
                          m: int):
    t = pl.program_id(0)
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(sig_ref[0], z_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        eta = scal_ref[t]               # per-task step size
        lam = scal_ref[m + t]           # per-task regularization weight
        theta = scal_ref[2 * m]         # momentum coefficient (t_j-1)/t_{j+1}
        grad = acc_ref[...] - c_ref[0].astype(jnp.float32)
        v = z_tile_ref[0].astype(jnp.float32) - eta * grad
        tau = eta * lam
        xn = (jnp.sign(v) * jnp.maximum(jnp.abs(v) - tau, 0.0)
              ).astype(xn_ref.dtype)
        xn_ref[0] = xn
        # momentum in the iterate dtype, on the already-cast x_next —
        # bitwise what the two-op path computes from the kernel output
        zn_ref[0] = xn + theta.astype(xn.dtype) * (xn - x_ref[0])


def _fista_batched_inplace_kernel(scal_ref, sig_ref, z_ref, z_tile_ref,
                                  x_ref, c_ref, w_ref, xn_ref, zn_ref,
                                  acc_ref, *, nk: int, m: int):
    del w_ref       # z_next's buffer: written through zn_ref, never read
    _fista_batched_kernel(scal_ref, sig_ref, z_ref, z_tile_ref, x_ref,
                          c_ref, xn_ref, zn_ref, acc_ref, nk=nk, m=m)


def _fista_batched_call(Sigmas, zs, xs, cs, etas, lam, theta, ws, *,
                        bp: int, br: int, bk: int, interpret: bool):
    """The fused FISTA step's pallas call; with a spare stack `ws`,
    x_next is written over `xs` and z_next over `ws`."""
    m, p, r = zs.shape
    bp = min(bp, p)
    br = min(br, r)
    bk = min(bk, p)
    assert p % bp == 0 and r % br == 0 and p % bk == 0, (m, p, r, bp, br, bk)
    ni, nj, nk = p // bp, r // br, p // bk

    scal = jnp.concatenate(
        [etas.astype(jnp.float32).reshape(m),
         jnp.broadcast_to(jnp.asarray(lam, jnp.float32).reshape(-1), (m,)),
         jnp.asarray(theta, jnp.float32).reshape(1)])

    out = jax.ShapeDtypeStruct((m, p, r), zs.dtype)
    tile = pl.BlockSpec((1, bp, br), lambda t, i, j, k: (t, i, j))
    operands = (scal, Sigmas, zs, zs, xs, cs)
    kernel, spare, aliases = _fista_batched_kernel, [], {}
    if ws is not None:
        # w is never read, so it stays in HBM; x_prev -> x', w -> z'
        kernel, spare, aliases = (_fista_batched_inplace_kernel,
                                  [pl.BlockSpec(memory_space=pl.ANY)],
                                  {4: 0, 6: 1})
        operands += (ws,)
    return pl.pallas_call(
        functools.partial(kernel, nk=nk, m=m),
        grid=(m, ni, nj, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # etas ++ lam ++ [theta]
            pl.BlockSpec((1, bp, bk), lambda t, i, j, k: (t, i, k)),
            pl.BlockSpec((1, bk, br), lambda t, i, j, k: (t, k, j)),
            tile,                                   # z (iterate tile)
            tile,                                   # x_prev
            tile,                                   # c
            *spare,
        ],
        out_specs=(tile, tile),
        out_shape=(out, out),
        scratch_shapes=[pltpu.VMEM((bp, br), jnp.float32)],
        input_output_aliases=aliases,
        compiler_params=_compiler_params(bp, br, bk),
        interpret=interpret,
    )(*operands)


@functools.partial(jax.jit,
                   static_argnames=("bp", "br", "bk", "interpret"))
def fista_step_batched_pallas(Sigmas, zs, xs, cs, etas, lam, theta, *,
                              bp: int = 128, br: int = 128, bk: int = 128,
                              interpret: bool = False):
    """One fused FISTA iteration for m tasks: prox step at the momentum
    point `zs` plus the extrapolation against the previous iterate `xs`.

    Sigmas: (m, p, p); zs/xs/cs: (m, p, r); etas: (m,) per-task step
    sizes; lam scalar or per-task (m,); theta the (traced) scalar
    momentum coefficient of this iteration. Returns (x_next, z_next),
    both (m, p, r), in fresh buffers.
    """
    return _fista_batched_call(Sigmas, zs, xs, cs, etas, lam, theta, None,
                               bp=bp, br=br, bk=bk, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("bp", "br", "bk", "interpret"))
def fista_step_batched_inplace_pallas(Sigmas, zs, xs, ws, cs, etas, lam,
                                      theta, *, bp: int = 128,
                                      br: int = 128, bk: int = 128,
                                      interpret: bool = False):
    """`fista_step_batched_pallas` writing into buffers its caller owns:
    x_next overwrites `xs` and z_next overwrites the spare stack `ws`
    (same shape, contents never read). Returns (x_next, z_next).

    x_next may take x_prev's buffer because tile (t, i, j) of `xs` is
    read only by the grid steps (t, i, j, .), which are the steps that
    write tile (t, i, j) of x_next. z_next may NOT take `zs`'s buffer:
    `zs` is the contraction operand, and every row block i reads all of
    it, so an early row block's z_next tiles would overwrite rows a
    later row block still has to read. Hence the third stack. A loop
    that alternates `zs` and `ws` between steps (the engine's pair
    schedule) keeps every iterate in a buffer it owns, and XLA inserts
    no loop-carry copy.
    """
    return _fista_batched_call(Sigmas, zs, xs, cs, etas, lam, theta, ws,
                               bp=bp, br=br, bk=bk, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("bp", "br", "bk", "interpret"))
def ista_step_batched_pallas(Sigmas, betas, cs, etas, lam, *, bp: int = 128,
                             br: int = 128, bk: int = 128,
                             interpret: bool = False):
    """Batched fused ISTA step over m independent tasks in ONE pallas call.

    Sigmas: (m, p, p), betas/cs: (m, p, r), etas: (m,) per-task step
    sizes, lam scalar or per-task (m,) regularization weights. The task
    index is the outermost grid dimension, so every task's (i, j, k)
    tile sweep reuses the same VMEM accumulator layout as the
    single-task kernel — the MXU sees one long stream of
    (bp, bk) x (bk, br) tiles instead of m separate dispatches.
    """
    m, p, r = betas.shape
    bp = min(bp, p)
    br = min(br, r)
    bk = min(bk, p)
    assert p % bp == 0 and r % br == 0 and p % bk == 0, (m, p, r, bp, br, bk)
    ni, nj, nk = p // bp, r // br, p // bk

    eta_lam = jnp.concatenate(
        [etas.astype(jnp.float32).reshape(m),
         jnp.broadcast_to(jnp.asarray(lam, jnp.float32).reshape(-1),
                          (m,))])

    return pl.pallas_call(
        functools.partial(_ista_batched_kernel, nk=nk, m=m),
        grid=(m, ni, nj, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),        # etas ++ [lam]
            pl.BlockSpec((1, bp, bk), lambda t, i, j, k: (t, i, k)),
            pl.BlockSpec((1, bk, br), lambda t, i, j, k: (t, k, j)),
            pl.BlockSpec((1, bp, br), lambda t, i, j, k: (t, i, j)),
            pl.BlockSpec((1, bp, br), lambda t, i, j, k: (t, i, j)),
        ],
        out_specs=pl.BlockSpec((1, bp, br), lambda t, i, j, k: (t, i, j)),
        out_shape=jax.ShapeDtypeStruct((m, p, r), betas.dtype),
        scratch_shapes=[pltpu.VMEM((bp, br), jnp.float32)],
        compiler_params=_compiler_params(bp, br, bk),
        interpret=interpret,
    )(eta_lam, Sigmas, betas, betas, cs)


@functools.partial(jax.jit,
                   static_argnames=("bp", "br", "bk", "interpret"))
def ista_step_pallas(Sigma, beta, c, eta, lam, *, bp: int = 128,
                     br: int = 128, bk: int = 128,
                     interpret: bool = False):
    """Sigma: (p, p), beta/c: (p, r). Returns the next ISTA iterate (p, r)."""
    p, r = beta.shape
    bp = min(bp, p)
    br = min(br, r)
    bk = min(bk, p)
    assert p % bp == 0 and r % br == 0 and p % bk == 0, (p, r, bp, br, bk)
    ni, nj, nk = p // bp, r // br, p // bk

    eta_lam = jnp.array([eta, lam], jnp.float32)

    return pl.pallas_call(
        functools.partial(_ista_kernel, nk=nk),
        grid=(ni, nj, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),            # (eta, lam)
            pl.BlockSpec((bp, bk), lambda i, j, k: (i, k)),   # Sigma tile
            pl.BlockSpec((bk, br), lambda i, j, k: (k, j)),   # beta (contraction)
            pl.BlockSpec((bp, br), lambda i, j, k: (i, j)),   # beta (iterate)
            pl.BlockSpec((bp, br), lambda i, j, k: (i, j)),   # c tile
        ],
        out_specs=pl.BlockSpec((bp, br), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((p, r), beta.dtype),
        scratch_shapes=[pltpu.VMEM((bp, br), jnp.float32)],
        compiler_params=_compiler_params(bp, br, bk),
        interpret=interpret,
    )(eta_lam, Sigma, beta, beta, c)
