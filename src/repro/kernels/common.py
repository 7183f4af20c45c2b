"""Shared routing/clipping helpers for the sample-streaming kernel
dispatchers (`logistic_grad`, `rank_update`). One definition site so
the dispatchers — and the engine block policies built on them — can
never desync.
"""
from __future__ import annotations

import jax

from repro import obs


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def kernels_by_default() -> bool:
    """The `use_kernel=None` default of every engine entry point: the
    Pallas kernels on TPU, the jnp oracles elsewhere. Kept apart from
    `on_tpu` (which picks interpret mode) so a CPU rehearsal can run
    the kernel path in interpret mode by patching this one function."""
    return on_tpu()


# the TPU's (8, 128) register tile: a block's last dimension must be a
# multiple of LANE or the whole axis, its second-to-last a multiple of
# 8 (`aligned_fit_block`) or the whole axis
LANE = 128


def lane_fit_block(size: int, block: int) -> int:
    """Largest multiple of 128 that divides `size` and is <= `block`
    (at least 128) — the legal tile for an axis that lands on the TPU's
    lanes. An axis with no 128-multiple divisor takes the whole axis,
    the only other tile the compiler accepts there."""
    if size % LANE:
        return size
    return LANE * fit_block(size // LANE, max(block // LANE, 1))


def fit_block(size: int, block: int) -> int:
    """Largest divisor of `size` that is <= `block` — the legal tile
    closest to the requested one. (NOT the halving loop of the older
    ista dispatcher: halving a non-divisor request like 48 against
    size 80 bottoms out at 1 and silently degrades the grid to
    single-element tiles; the divisor scan returns 40.)"""
    b = min(block, size)
    while size % b:
        b -= 1
    return b


# the minimum tile worth running a grid over: when an axis has no
# 8-aligned divisor at or above this (relative to the request), the
# best tile a TPU grid could legally use is a sliver and the grid it
# implies is quietly catastrophic — e.g. size 1016 = 8 * 127 against a
# 128 request: the divisor scan finds 127 (which breaks the 8-row
# sublane alignment) and the best ALIGNED divisor is 8, a 127-step
# sliver sweep where the caller asked for ~8 steps of 128
MIN_TILE = 32


def aligned_fit_block(size: int, block: int) -> int:
    """Largest divisor of `size` that is <= `block` AND keeps the TPU's
    8-row alignment (the tile the hardware grid could actually use).
    Falls back to the plain divisor scan when the axis itself is not
    8-aligned (such shapes are ragged and never reach a kernel)."""
    if size % 8 or block < 8:
        return fit_block(size, block)
    return 8 * fit_block(size // 8, block // 8)


def validate_block(block, arity: int, doc: str, *,
                   arities: tuple | None = None) -> tuple:
    """Shared `block=`-argument validation for ALL kernel dispatchers:
    anything that is not an accepted form — bools, floats, wrong-arity
    tuples — raises instead of being silently coerced (the historical
    `block[0]` bug let a rank-style pair tile the wrong axes). Entries
    must be POSITIVE — a zero block would divide-by-zero inside the
    divisor scan and a negative one would silently reroute to the
    oracle. `doc` names the expected tuple form in the error.

    Two acceptance modes, one definition site (so the lint tier has a
    single pattern to check — see tools/repro_lint):

    * `arities=None` (rank_update / ista_step / group / flash style):
      an int broadcasts to all `arity` axes, a tuple must have exactly
      `arity` entries.
    * `arities=(0, 1, arity)`-style (logistic style, dispatchers with
      budgeted per-axis defaults): 0 admits `block=None` (every axis
      defaulted), 1 admits a bare int as a FIRST-axis request (the
      remaining axes defaulted, NOT broadcast), `arity` admits the full
      tuple. The returned length-`arity` tuple pads defaulted axes with
      None for the resolver to budget.
    """
    def ok(b):
        return isinstance(b, int) and not isinstance(b, bool) and b >= 1
    if arities is None:
        if ok(block):
            return (block,) * arity
        if (isinstance(block, tuple) and len(block) == arity
                and all(ok(b) for b in block)):
            return block
    else:
        if block is None and 0 in arities:
            return (None,) * arity
        if ok(block) and 1 in arities:
            return (block,) + (None,) * (arity - 1)
        if (isinstance(block, tuple) and len(block) == arity
                and arity in arities and all(ok(b) for b in block)):
            return block
    raise TypeError(
        f"block must be a positive int or a {doc} tuple of positive "
        f"ints — got {block!r}")


def degrades_to_slivers(size: int, block: int) -> bool:
    """True when fitting the requested `block` to `size` degrades to a
    sliver tile: the largest aligned divisor falls below MIN_TILE AND
    below a quarter of the request (a >4x longer grid than asked for).
    Such shapes belong to the oracle — an explicitly tiny request, an
    axis that IS tiny, or a modest clip (48-on-80 -> 40) is honoured;
    only the silent collapse (128-on-1016 -> 8) is routed away."""
    return aligned_fit_block(size, block) < min(block // 4, size, MIN_TILE)


def is_ragged_samples(n: int, p: int) -> bool:
    """THE routing predicate for the sample-streaming kernels (logistic
    gradient, rank-n update): shapes whose sample or feature axis the
    TPU tiling cannot legally cover go to the jnp oracle. Shared with
    the engine's block policies so the two can never desync."""
    return bool(n % 8 or p % 8)


def record_route(kernel: str, reason: str | None, *, blocks=None,
                 vmem_mib=None) -> None:
    """THE telemetry funnel for dispatcher routing decisions — the one
    audited exception to lint code RL108 (no `repro.obs` calls in
    jit-reachable code). Dispatchers run at trace time under jit, so
    these counters count COMPILATIONS, not executions; every argument
    is a Python-concrete shape/policy value, never a tracer, which is
    why routing through here is safe where a raw obs call is not.

    `reason` is None on the kernel path, else why the oracle won
    (`ragged` / `sliver` / `vmem_budget` / `backend`); `blocks` is the
    resolved tile tuple; `vmem_mib`, where given, the scoped VMEM limit
    the kernel call asks for (`default` for none)."""
    if not obs.enabled():
        return
    extra = {} if vmem_mib is None else {"vmem_mib": str(vmem_mib)}
    obs.inc("dispatch.route", kernel=kernel,
            outcome="kernel" if reason is None else "oracle",
            reason=reason or "kernel",
            blocks="none" if blocks is None
            else "x".join(str(b) for b in blocks), **extra)
