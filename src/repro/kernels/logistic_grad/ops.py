"""Dispatcher for the fused all-tasks logistic gradient.

Same convention as `kernels/ista_step/ops.py`: the pallas kernel on
MXU-friendly shapes (interpret mode off-TPU so the same BlockSpecs
execute everywhere), the jnp oracle on ragged shapes — and the oracle
is bitwise the engine's historical inline einsum gradient, so routing
never perturbs solver iterates.

Block policy (DESIGN.md §12): `block` is None (budgeted default), an
int sample tile bn, or an explicit (bn, bp) pair — bn tiles the sample
axis, bp the feature axis. Anything else raises (the old dispatcher
documented `block: int` but silently coerced tuples via `block[0]`, so
a rank-style (bp, bn) pair picked the FEATURE tile as the sample
tile). The feature axis no longer has a hard p cliff: the routing
predicate is a per-tile VMEM budget — full-lane slabs while they fit,
feature-tiled slabs past that, the oracle only when no legal tiling
fits (ragged axes, sliver-degraded sample tiles, or p so large the
gradient accumulator itself outgrows the budget).
"""
from __future__ import annotations

from typing import Optional, Tuple

from repro.kernels.common import (
    LANE, aligned_fit_block, degrades_to_slivers, lane_fit_block, on_tpu,
    record_route, validate_block,
)
from repro.kernels.common import is_ragged_samples  # re-export (tests/engine)
from repro.kernels.logistic_grad.kernel import (
    logistic_grad_pallas, logistic_grad_unfused_pallas,
)
from repro.kernels.logistic_grad.ref import logistic_grad_ref

# per-dispatch VMEM budget for one grid step of the kernel (half of the
# ~16 MB/core, leaving slack for operand double-buffering). With the
# default bn = 128, full-lane slabs fit to p ~= 2.7k; past that the
# kernel feature-tiles (the whole old MAX_FULL_LANE_P regime stays on
# the kernel — tiled instead of falling off a cliff onto the oracle);
# only p whose PADDED gradient accumulator alone busts the budget
# (p ≳ 16k, see below) routes away entirely
LOGISTIC_VMEM_BUDGET = 8 * 1024 * 1024


def kernel_vmem_bytes(p: int, bn: int, bp: int) -> int:
    """Estimated VMEM footprint of one fused-kernel grid step. The
    (bn, bp) X slab is counted double-buffered at its true f32 size;
    every trailing-singleton buffer — the gradient accumulator (p rows
    total across its pi tiles), the z carry and y tile (bn rows), the
    b and out tiles (bp rows) — is counted at its PADDED width: a
    (r, 1) f32 buffer occupies full (8, 128) register tiles on TPU,
    i.e. 512 bytes per row, not 4. Only the bn TILE of the sample axis
    is resident, so n itself never enters."""
    return 8 * bn * bp + 512 * (p + 2 * bn + 3 * bp)


# `block=` normalization: the shared validator's partial-arity mode.
# block=None defaults both axes, a bare int is a bn request with the
# feature tile budgeted (NOT broadcast — tuples must spell out both
# entries), a (bn, bp) pair is taken whole; a returned None request
# means "use the budgeted default for that axis". Note the tuple
# order: bn (sample axis) first, bp (feature axis) second — a
# rank_update-style (bp, bn) pair would tile the wrong axes, which is
# exactly the silent `block[0]` coercion this validation replaces.
_BLOCK_ARITIES = (0, 1, 2)


def _budget_bp(p: int, bn: int) -> int:
    """Largest lane-legal feature tile (a 128-multiple divisor of p, or
    p itself) whose grid step fits the VMEM budget — bp = p (the
    resident full-lane layout) whenever it fits. When none fits, the
    smallest legal tile, which the routing predicate sends away."""
    bp = p
    while (p % LANE == 0 and bp > LANE
           and kernel_vmem_bytes(p, bn, bp) > LOGISTIC_VMEM_BUDGET):
        bp = lane_fit_block(p, bp - LANE)
    return bp


def resolve_logistic_blocks(n: int, p: int, block=None) -> Tuple[int, int]:
    """Normalize a block policy to concrete (bn, bp) tile sizes.

    `block` is None (bn = 128 request, bp budgeted), an int bn request,
    or an explicit (bn, bp) pair — e.g. an autotuned winner from
    `repro.kernels.autotune.autotune_logistic_block`. Each entry is
    fitted to the TPU's (8, 128) tiling: bn, a sublane axis, to the
    largest 8-ALIGNED divisor of n (a plain divisor scan can land on
    alignment traps like 126 for size 504); bp, the lane axis, to the
    largest 128-multiple divisor of p or the whole axis. A defaulted bp
    is the largest such tile whose slab fits `LOGISTIC_VMEM_BUDGET`
    (full lanes for small p — the historical layout — feature tiles
    past it).
    """
    bn_req, bp_req = validate_block(block, 2, "(bn, bp)",
                                    arities=_BLOCK_ARITIES)
    bn = aligned_fit_block(n, 128 if bn_req is None else bn_req)
    bp = _budget_bp(p, bn) if bp_req is None \
        else lane_fit_block(p, bp_req)
    return bn, bp


def _route_and_resolve(n: int, p: int,
                       block) -> Tuple[Optional[str], int, int]:
    """ONE block resolution feeding both the routing verdict and the
    dispatch tiles, so the predicate can never approve a tiling the
    dispatcher then resolves differently. Returns (reason, bn, bp)
    where reason is None on the kernel path, else the telemetry label
    for why the oracle won. Routed when: ragged axes (`ragged`);
    sample tiles degraded to slivers vs the request (e.g. n = 1016 =
    8*127 against the 128 default; `sliver`); or a resolved tiling over
    the per-tile VMEM budget (`vmem_budget`) — p so large the gradient
    accumulator outgrows it, or a p with no 128-multiple divisor (e.g.
    p = 8168 = 8*1021) whose only legal feature tile, the whole axis,
    does not fit. Lane tiles are 128-multiples or the whole axis, so the
    feature axis never degrades to a sliver. The clause SET is what
    routes; the order only picks which label wins when several
    apply."""
    bn_req, _ = validate_block(block, 2, "(bn, bp)", arities=_BLOCK_ARITIES)
    bn, bp = resolve_logistic_blocks(n, p, block)
    if is_ragged_samples(n, p):
        reason = "ragged"
    elif degrades_to_slivers(n, 128 if bn_req is None else bn_req):
        reason = "sliver"
    elif kernel_vmem_bytes(p, bn, bp) > LOGISTIC_VMEM_BUDGET:
        reason = "vmem_budget"
    else:
        reason = None
    return reason, bn, bp


def routes_to_oracle(n: int, p: int, block=None) -> bool:
    """True when this (n, p) never reaches the pallas kernel (see
    `_route_and_resolve` for the clauses). The engine's block policy
    shares this so it never sweeps a shape the dispatcher will not
    serve."""
    return _route_and_resolve(n, p, block)[0] is not None


def logistic_grad(Xs, ys, B, *, block=None,
                  interpret: bool | None = None):
    """All-tasks logistic gradient -X'(y sigmoid(-y Xb))/n.

    Xs (m, n, p), ys (m, n) in {-1, +1}, B (m, p) -> (m, p). `block` is
    None, an int sample tile bn, or a (bn, bp) pair (e.g. an autotuned
    winner from `repro.kernels.autotune.autotune_logistic_block`);
    ragged, sliver-degraded, and over-VMEM-budget shapes fall back to
    `logistic_grad_ref`.
    """
    m, n, p = Xs.shape
    interp = (not on_tpu()) if interpret is None else interpret
    reason, bn, bp = _route_and_resolve(n, p, block)
    record_route("logistic_grad", reason, blocks=(bn, bp))
    if reason is not None:
        return logistic_grad_ref(Xs, ys, B)
    return logistic_grad_pallas(Xs, ys, B, bn=bn, bp=bp, interpret=interp)


def logistic_grad_unfused(Xs, ys, B, *, block=None,
                          interpret: bool | None = None):
    """Two-dispatch (matvec + back-projection) pallas baseline with the
    same routing policy — exists for the fused-vs-unfused benchmark pair
    and as a second kernel-path parity anchor in tests."""
    m, n, p = Xs.shape
    interp = (not on_tpu()) if interpret is None else interpret
    reason, bn, bp = _route_and_resolve(n, p, block)
    record_route("logistic_grad_unfused", reason, blocks=(bn, bp))
    if reason is not None:
        return logistic_grad_ref(Xs, ys, B)
    return logistic_grad_unfused_pallas(Xs, ys, B, bn=bn, bp=bp,
                                        interpret=interp)
