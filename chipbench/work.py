"""Operations and bytes of the service's algorithmic work, from shapes and
iteration counts alone, so that they read the same whichever
implementation (Pallas kernel or XLA) does the work. Float32 operands
are 4 bytes. Each function returns (flops, bytes).

The least time of a piece of work on a chip is the larger of its
operations over the peak FLOP/s and its bytes over the HBM bandwidth;
a phase's least time is summed over its iterations.
"""
from __future__ import annotations

F32 = 4
POWER_ITERS = 64        # the engine's power iteration per refit


def least_time(phases, peaks) -> float:
    """Sum over phases of max(flops / peak, bytes / bandwidth)."""
    return sum(max(f / peaks["flops_per_s"], b / peaks["hbm_bytes_per_s"])
               for f, b in phases)


def matvec(m: int, p: int):
    """One pass of a (m, p, p) stack against one vector per task: the
    stack is read once."""
    return 2 * m * p * p, F32 * m * p * p


def debias_step(m: int, p: int):
    """One FISTA step of the M solve, p right-hand sides per task:
    Sigma @ Z, then the prox and the momentum. It reads Sigma, the
    momentum point and the previous iterate, and writes the new iterate
    and momentum point: five (m, p, p) stacks."""
    return 2 * m * p ** 3, 5 * F32 * m * p * p


def refit_phases(m: int, p: int, refits: int, lasso_iters: int,
                 debias_iters: int):
    """Phases of `refits` refits that ran `lasso_iters` and
    `debias_iters` FISTA steps in all: the power iteration, the lasso,
    the M solve, and the debias b + M(c - Sigma b) (two matvecs). The
    threshold is O(m p) and left out."""
    def times(k, fb):
        return k * fb[0], k * fb[1]
    return [times(refits * POWER_ITERS, matvec(m, p)),
            times(lasso_iters, matvec(m, p)),
            times(debias_iters, debias_step(m, p)),
            times(2 * refits, matvec(m, p))]


def fold_chunk(m: int, n: int, p: int):
    """Folding one (m, n, p) chunk: X'X and X'y, reading the chunk and
    reading and writing the Sigma and c stacks."""
    flops = 2 * m * n * p * p + 2 * m * n * p
    bytes_ = F32 * (m * n * p + m * n) + 2 * F32 * (m * p * p + m * p)
    return flops, bytes_
