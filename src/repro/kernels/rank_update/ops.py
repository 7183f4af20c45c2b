"""Dispatcher for the fused rank-n sufficient-statistics update.

Same convention as `kernels/ista_step/ops.py` and
`kernels/logistic_grad/ops.py`: pallas on MXU-friendly shapes
(interpret mode off-TPU), the jnp oracle on ragged shapes — and the
oracle is bitwise the historical `sufficient_stats` einsum pair, so the
CPU default path perturbs nothing downstream.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.common import (
    aligned_fit_block, degrades_to_slivers, is_ragged_samples,
    lane_fit_block, on_tpu, record_route, validate_block,
)
from repro.kernels.rank_update.kernel import (
    rank_update_pallas, rank_update_unfused_pallas,
)
from repro.kernels.rank_update.ref import rank_update_ref


# per-dispatch VMEM budget for one grid step, same 8 MB envelope as the
# logistic kernel (half the ~16 MB core, slack for double-buffering)
RANK_VMEM_BUDGET = 8 * 1024 * 1024


def rank_vmem_bytes(bp: int, bn: int) -> int:
    """Estimated VMEM footprint of one fused-kernel grid step: the two
    (bn, bp) X slabs (xi, xj) double-buffered at their true f32 size
    with the lane axis padded to full 128-lane register tiles, the
    (bp, bp) Sigma output tile, and the trailing-singleton y/c buffers
    at their PADDED 512 B/row width (a (r, 1) f32 buffer occupies full
    (8, 128) register tiles on TPU). The byte model is the checked
    contract shared with tools/repro_lint's static tiling pass — an
    explicit `block=` the model rejects routes to the bitwise oracle
    instead of compiling a Mosaic OOM."""
    lanes = ((bp + 127) // 128) * 128
    return 16 * bn * lanes + 4 * bp * lanes + 512 * (bn + bp)


def resolve_rank_blocks(n: int, p: int, block) -> Tuple[int, int]:
    """Normalize a block policy to concrete (bp, bn) tile sizes.
    `block` is one int (applied to both axes) or an explicit (bp, bn)
    pair — note the order, feature axis first — e.g. an autotuned
    winner from `repro.kernels.autotune.autotune_rank_block`; anything
    else raises instead of being silently coerced (the logistic
    dispatcher's old `block[0]` bug, audited here too). Each entry is
    fitted to the TPU's (8, 128) tiling: bp, the lane axis of the X
    slabs and the Sigma tile, to the largest 128-multiple divisor of p
    or the whole axis; bn, a sublane axis, to the largest 8-aligned
    divisor of n."""
    bp, bn = validate_block(block, 2, "(bp, bn)")
    return lane_fit_block(p, bp), aligned_fit_block(n, bn)


def _rank_route_reason(n: int, p: int, block=128) -> Optional[str]:
    """Routing verdict plus its telemetry label: None on the kernel
    path, else `ragged` / `sliver` / `vmem_budget` (same clause set as
    ever; the order only picks the label when several apply)."""
    _, bn_req = validate_block(block, 2, "(bp, bn)")
    bp, bn = resolve_rank_blocks(n, p, block)
    if is_ragged_samples(n, p):
        return "ragged"
    if degrades_to_slivers(n, bn_req):
        return "sliver"
    if rank_vmem_bytes(bp, bn) > RANK_VMEM_BUDGET:
        return "vmem_budget"
    return None


def rank_routes_to_oracle(n: int, p: int, block=128) -> bool:
    """Routing predicate shared with the engine's rank block policy:
    ragged shapes, shapes whose requested sample tiles degrade to
    sliver grids (e.g. n = 1016 against a 128 request), and resolved
    tilings whose grid step busts `RANK_VMEM_BUDGET` (an explicit
    block= large enough that the X slabs or the Sigma tile outgrow
    VMEM, or a p with no 128-multiple divisor whose whole-axis tile
    does) go to the jnp oracle."""
    return _rank_route_reason(n, p, block) is not None


def rank_update(Xs, ys, weights=None, *, block=128,
                interpret: bool | None = None,
                use_kernel: bool | None = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-task statistics (n^-1 X'WX, n^-1 X'Wy) for a sample chunk.

    Xs (m, n, p), ys (m, n), optional weights (m, n) ->
    (Sigmas (m, p, p), cs (m, p)). Routes to the fused pallas kernel on
    MXU-friendly shapes when `use_kernel` (default: only on TPU — the
    XLA einsum oracle is the fast CPU path); ragged shapes always take
    the oracle. `block` is an int or an explicit (bp, bn) pair.
    """
    m, n, p = Xs.shape
    # resolve (and so validate) blocks BEFORE the oracle short-circuit:
    # a malformed block must raise on every path, not only on TPU
    bp, bn = resolve_rank_blocks(n, p, block)
    if use_kernel is None:
        use_kernel = common.kernels_by_default()
    interp = (not on_tpu()) if interpret is None else interpret
    reason = _rank_route_reason(n, p, block)
    if not use_kernel or reason is not None:
        record_route("rank_update", reason or "backend", blocks=(bp, bn))
        return rank_update_ref(Xs, ys, weights)
    record_route("rank_update", None, blocks=(bp, bn))
    return rank_update_pallas(Xs, ys, weights, bp=bp, bn=bn,
                              interpret=interp)


def rank_update_unfused(Xs, ys, weights=None, *, block=128,
                        interpret: bool | None = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Two-dispatch (covariance + correlation) pallas baseline with the
    same routing policy — exists for the fused-vs-unfused benchmark
    pair and as a second kernel-path parity anchor in tests."""
    m, n, p = Xs.shape
    bp, bn = resolve_rank_blocks(n, p, block)
    interp = (not on_tpu()) if interpret is None else interpret
    reason = _rank_route_reason(n, p, block)
    record_route("rank_update_unfused", reason, blocks=(bp, bn))
    if reason is not None:
        return rank_update_ref(Xs, ys, weights)
    return rank_update_unfused_pallas(Xs, ys, weights, bp=bp, bn=bn,
                                      interpret=interp)
