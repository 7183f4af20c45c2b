"""Block-size autotuning for the batched Pallas solver kernels.

Three kernel families are shape-polymorphic over their problem sizes
and their best tilings depend on the backend and dtype:

  * `fista_step` — the fused ISTA/FISTA solver step, swept over
    (bp, br, bk) for a (m, p, r) solve;
  * `logistic_grad` — the fused all-tasks logistic gradient, swept over
    (bn, bp) sample/feature tiles for a (m, n, p) batch (large-p shapes
    sweep real feature tilings under the per-tile VMEM budget);
  * `rank_update` — the fused rank-n sufficient-statistics update,
    swept over (bp, bn) for a (m, n, p) chunk.

Each `autotune_*` entry point times the candidate tilings for a given
problem key once, then serves the winner from an in-process cache
backed by a JSON file under the repo cache dir (`.cache/autotune.json`,
override with $REPRO_CACHE_DIR), so a process restart never re-times a
known key. Every candidate obeys the TPU's (8, 128) tiling rule: a tile
on a lane axis is a multiple of 128 or the whole axis, a tile on a
sublane axis a multiple of 8. Cache keys are NAMESPACED PER KERNEL
(`"<kernel>/<backend>_<dims>_<dtype>"`); legacy un-namespaced entries
(pre-namespace files were written only by the fista sweep) are migrated
to `fista_step/...` on load.

Sweeps run only from eager calls: `warmup_cache` (what
`StreamingDsmlService` calls at construction) and direct `autotune_*`
calls. The engine (`core/engine.py`) uses these as its default block
policies with `sweep=False`: `solve_lasso_batched(block=None)` /
`solve_logistic_lasso_batched(block=None)` / `sufficient_stats
(block=None)` on the kernel path — which often run under a caller's
jit trace — look the winner up and serve the deterministic default on
a miss; an explicit `block=` always wins and never touches the cache.
"""
from __future__ import annotations

import hashlib
import json
import os
import re
import time
from pathlib import Path
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels.common import (
    LANE, aligned_fit_block, lane_fit_block, on_tpu,
)
from repro.kernels.ista_step.kernel import fista_step_batched_pallas
from repro.kernels.ista_step.ops import (
    STEP_VMEM_BUDGET, resolve_blocks, step_vmem_bytes,
)
from repro.kernels.logistic_grad.kernel import logistic_grad_pallas
from repro.kernels.logistic_grad.ops import (
    LOGISTIC_VMEM_BUDGET, kernel_vmem_bytes, resolve_logistic_blocks,
    routes_to_oracle,
)
from repro.kernels.rank_update.kernel import rank_update_pallas

_REPO_ROOT = Path(__file__).resolve().parents[3]
CACHE_FILE = "autotune.json"

# block candidates per grid axis, intersected with the divisors of the
# actual dimension: lane axes take 128-multiples (or the whole axis),
# sublane axes 8-multiples, so every candidate is a tiling the TPU
# compiler accepts
LANE_CANDIDATES = (128, 256, 512)
SUBLANE_CANDIDATES = (32, 64, 128, 256)
# the many-right-hand-side step's output tile (bp, br) and its
# streamed contraction tile bk; at most MAX_CANDIDATES are swept
WIDE_BP = (256, 512, 1024)
WIDE_BR = (512, 1024, 2048)
WIDE_BK = (128, 256, 512)
MAX_CANDIDATES = 7

_memory_cache: Dict[str, tuple] = {}


def cache_path() -> Path:
    return Path(os.environ.get("REPRO_CACHE_DIR",
                               _REPO_ROOT / ".cache")) / CACHE_FILE


def cache_key(kernel: str, backend: str, dims: Dict[str, int],
              dtype, space=None) -> str:
    """Per-kernel-namespaced key: "<kernel>/<backend>_m4_p128_..._f32".
    Entries for different kernels can never collide even when their
    dimension tuples coincide (e.g. a (m, n, p) logistic sweep vs a
    (m, p, r) solver sweep with equal numbers). With `space`, the
    candidate list the sweep times, the key ends in "@<digest>" of that
    list: a winner of another list is never served, so a change to the
    candidates re-tunes by itself."""
    dim_s = "_".join(f"{k}{v}" for k, v in dims.items())
    key = f"{kernel}/{backend}_{dim_s}_{jnp.dtype(dtype).name}"
    if space is None:
        return key
    digest = hashlib.sha1(json.dumps([list(c) for c in space]).encode())
    return f"{key}@{digest.hexdigest()[:8]}"


def clear_memory_cache() -> None:
    _memory_cache.clear()


def _migrate(entries: dict) -> Tuple[dict, bool]:
    """Namespace legacy keys. Files written before the per-kernel
    namespace held only fista sweeps under bare "<backend>_..." keys;
    prefix them so old caches keep serving (and never shadow or absorb
    the new kernels' entries). Pre-feature-tiling `logistic_grad/`
    entries were a bare int bn with an implicit full-lane bp = p: widen
    them through the budgeted resolver ((n, p) read back off the key),
    NOT to a literal [bn, p] — a legacy winner like bn = 256 at
    p = 4096 pairs with a full-lane slab that busts the new VMEM
    budget, and a migrated entry the dispatcher silently routes to the
    oracle would permanently lose that shape its kernel path."""
    migrated, changed = {}, False
    for k, v in entries.items():
        rewritten = False
        if "/" not in k:
            k, changed, rewritten = f"fista_step/{k}", True, True
        if k.startswith("logistic_grad/") and not isinstance(v, list):
            dims = re.search(r"_n(\d+)_p(\d+)_", k)
            if dims:
                n_k, p_k = int(dims.group(1)), int(dims.group(2))
                v = list(resolve_logistic_blocks(n_k, p_k, int(v)))
                changed, rewritten = True, True
        if rewritten:
            obs.inc("autotune.cache", kernel=k.split("/", 1)[0],
                    event="migrated")
        migrated[k] = v
    return migrated, changed


def _load_disk() -> dict:
    try:
        with open(cache_path()) as f:
            entries = json.load(f)
    except (OSError, ValueError):
        return {}
    entries, changed = _migrate(entries)
    if changed:
        _save_disk(entries)      # rewrite once; best-effort if read-only
    return entries


def _save_disk(entries: dict) -> None:
    path = cache_path()
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(".tmp")
        tmp.write_text(json.dumps(entries, indent=2, sort_keys=True))
        os.replace(tmp, path)
    except OSError:
        pass  # read-only checkout: the in-process cache still serves


def _divisor_candidates(size: int, cands=SUBLANE_CANDIDATES) -> List[int]:
    return [b for b in cands if b <= size and size % b == 0] or [size]


def block_candidates(p: int, r: int) -> List[Tuple[int, int, int]]:
    """Legal (bp, br, bk) tilings to sweep for a (p, r) solve.

    One right-hand side (the lasso) reads Sigma once whatever its tile:
    bk is tied to bp and both take lane candidates. Many right-hand
    sides (r >= 128, the M solve) read Sigma r / br times and z p / bp
    times per step, so the output tile grows as wide as the VMEM budget
    allows over a streamed contraction tile of 128 to 512; of those,
    the `MAX_CANDIDATES` with the fewest such passes (then the least
    VMEM) are swept."""
    if r < LANE:
        bps = _divisor_candidates(p, LANE_CANDIDATES)
        brs = [1] if r == 1 else _divisor_candidates(r, LANE_CANDIDATES)
        return [(bp, br, bp) for bp in bps for br in brs
                if step_vmem_bytes(bp, br, bp) <= STEP_VMEM_BUDGET]
    bps = [b for b in WIDE_BP if p % b == 0] or [
        aligned_fit_block(p, WIDE_BP[-1])]
    brs = [b for b in WIDE_BR if r % b == 0] or [
        lane_fit_block(r, WIDE_BR[-1])]
    bks = [b for b in WIDE_BK if p % b == 0] or [
        lane_fit_block(p, WIDE_BK[-1])]
    cands = [(bp, br, bk) for bp in bps for br in brs for bk in bks
             if step_vmem_bytes(bp, br, bk) <= STEP_VMEM_BUDGET]
    cands.sort(key=lambda c: (p // c[0] + r // c[1], step_vmem_bytes(*c)))
    return cands[:MAX_CANDIDATES]


def logistic_candidates(n: int, p: int) -> List[Tuple[int, int]]:
    """Legal (bn, bp) tilings to sweep for a (m, n, p) logistic-gradient
    batch, filtered to the kernel's per-tile VMEM budget. The feature
    axis (lanes) sweeps 128-multiple tiles up to 4096 and the full-lane
    bp = p layout, so small p sweeps the historical resident slab and
    large p sweeps real feature tilings."""
    bps = [b for b in (LANE, 256, 512, 1024, 2048, 4096)
           if b < p and p % b == 0] + [p]
    pairs = [(bn, bp) for bn in _divisor_candidates(n) for bp in bps
             if kernel_vmem_bytes(p, bn, bp) <= LOGISTIC_VMEM_BUDGET]
    return pairs or [resolve_logistic_blocks(n, p)]


def rank_candidates(n: int, p: int) -> List[Tuple[int, int]]:
    """Legal (bp, bn) tilings to sweep for a (m, n, p) rank-n update.
    The feature tile stays at one 128-lane tile (the whole axis when p
    has no 128-multiple divisor): on v5e the chip's compiler fails an
    internal check on the kernel's transposed X tile for some wider
    feature tiles (bp = 256 with bn = 256, bp = 512 with bn = 128), so
    the sweep covers the sample tile only, inside the kernel's VMEM
    budget."""
    from repro.kernels.rank_update.ops import (
        RANK_VMEM_BUDGET, rank_vmem_bytes, resolve_rank_blocks,
    )
    bp = lane_fit_block(p, LANE)
    return [(bp, bn) for bn in _divisor_candidates(n)
            if rank_vmem_bytes(bp, bn) <= RANK_VMEM_BUDGET] \
        or [resolve_rank_blocks(n, p, 128)]


def _time_candidate(fn, reps: int) -> float:
    """Best-of-`reps` wall time of `fn()` in microseconds (warm-up call
    synced first so compile time never counts). Module-level so tests
    can count sweep invocations."""
    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _autotune(kernel: str, dims: Dict[str, int], default, candidates,
              make_sweep: Callable, *, dtype, backend: str | None,
              interpret: bool | None, reps: int, use_disk: bool,
              sweep: bool, space=None):
    """Shared cache-then-sweep policy behind every `autotune_*` entry
    point. `make_sweep(interp)` builds the synthetic sweep inputs and
    returns a `candidate -> timing thunk` factory — called only when a
    sweep actually runs, so warm-cache hits on the engine hot path
    never pay a problem-sized allocation. The winner is written back to
    both caches, under a key that names the candidate list where `space`
    is that list (`cache_key`).

    With `sweep=False` (the engine's block policies, which run under a
    caller's jit trace where a timed candidate would return a tracer) a
    miss serves the deterministic default, uncached, so a later eager
    `warmup_cache` can still tune the key.

    Multi-controller guard: a winner becomes a STATIC compile
    parameter, and a timing sweep is not deterministic across hosts —
    divergent winners would compile divergent executables for one SPMD
    program. With more than one jax process every host returns the same
    deterministic default instead of sweeping.
    """
    if jax.process_count() > 1:
        obs.inc("autotune.cache", kernel=kernel, event="default_multiprocess")
        return default
    backend = jax.default_backend() if backend is None else backend
    key = cache_key(kernel, backend, dims, dtype, space)
    if key in _memory_cache:
        obs.inc("autotune.cache", kernel=kernel, event="hit_memory")
        return _memory_cache[key]
    disk = _load_disk() if use_disk else {}
    if key in disk:
        v = disk[key]
        blk = tuple(int(b) for b in v) if isinstance(v, list) else int(v)
        _memory_cache[key] = blk
        obs.inc("autotune.cache", kernel=kernel, event="hit_disk")
        return blk

    if not sweep:
        obs.inc("autotune.cache", kernel=kernel, event="miss_default")
        return default

    obs.inc("autotune.cache", kernel=kernel, event="miss_sweep")
    interp = (backend != "tpu") if interpret is None else interpret
    fn_for = make_sweep(interp)
    best_us, best = float("inf"), default
    with obs.span("autotune.sweep", kernel=kernel):
        for cand in candidates:
            us = _time_candidate(fn_for(cand), reps)
            obs.observe("autotune.candidate_us", us, kernel=kernel,
                        candidate="x".join(str(b) for b in cand)
                        if isinstance(cand, tuple) else str(cand))
            if us < best_us:
                best_us, best = us, cand
    _memory_cache[key] = best
    if use_disk:
        disk[key] = list(best) if isinstance(best, tuple) else best
        _save_disk(disk)
    return best


def warmup_cache(m: int, p: int, n: int | None = None, *,
                 dtype=jnp.float32, reps: int = 2) -> None:
    """Eagerly tune the solve shapes a DSML workload of m tasks in p
    dims hits — the r=1 lasso batch and the r=p multi-RHS debias solve,
    plus (when the chunk size `n` is known) the rank-n ingest and
    logistic-gradient shapes — so later JITTED engine calls find a warm
    cache. Large-p logistic shapes (past the old full-lane cliff) warm
    like any other now that the kernel feature-tiles its slabs.

    This is the intended production entry point: every in-repo solver
    is jitted and the engine's block policies only look winners up
    (see `_autotune`), so without an eager warm-up the engine keeps the
    deterministic 128 default. Call once at startup
    (`StreamingDsmlService` does, on TPU). No-op off-TPU, where the
    engine's default path is the jnp oracle and a sweep would time the
    slow interpreter for nothing.
    """
    if not on_tpu():
        return
    autotune_block(m, p, 1, dtype=dtype, reps=reps)
    autotune_block(m, p, p, dtype=dtype, reps=reps)
    if n is not None:
        autotune_logistic_block(m, n, p, dtype=dtype, reps=reps)
        autotune_rank_block(m, n, p, dtype=dtype, reps=reps)


def autotune_block(m: int, p: int, r: int, *, dtype=jnp.float32,
                   backend: str | None = None,
                   interpret: bool | None = None,
                   candidates: List[Tuple[int, int, int]] | None = None,
                   reps: int = 2, use_disk: bool = True, sweep: bool = True
                   ) -> Tuple[int, int, int]:
    """Winning (bp, br, bk) tiling for a batched FISTA solve step of
    this (m, p, r) shape (kernel namespace `fista_step`, keyed by the
    candidate list as well as the shape)."""
    def make_sweep(interp):
        k0, k1, k2 = jax.random.split(jax.random.PRNGKey(0), 3)
        Sigmas = jax.random.normal(k0, (m, p, p), dtype)
        zs = jax.random.normal(k1, (m, p, r), dtype)
        cs = jax.random.normal(k2, (m, p, r), dtype)
        etas = jnp.full((m,), 0.01, dtype)
        return lambda cand: lambda: fista_step_batched_pallas(
            Sigmas, zs, zs, cs, etas, 0.1, 0.5, bp=cand[0], br=cand[1],
            bk=cand[2], interpret=interp)

    cands = block_candidates(p, r) if candidates is None else candidates
    return _autotune(
        "fista_step", {"m": m, "p": p, "r": r},
        resolve_blocks(p, r, 128), cands, make_sweep, dtype=dtype,
        backend=backend, interpret=interpret, reps=reps,
        use_disk=use_disk, sweep=sweep, space=cands)


def autotune_logistic_block(m: int, n: int, p: int, *, dtype=jnp.float32,
                            backend: str | None = None,
                            interpret: bool | None = None,
                            candidates: List[Tuple[int, int]] | None = None,
                            reps: int = 2, use_disk: bool = True,
                            sweep: bool = True) -> Tuple[int, int]:
    """Winning (bn, bp) tiling for a (m, n, p) fused logistic-gradient
    batch (kernel namespace `logistic_grad`). Feature-tiled large-p
    shapes sweep too — the old full-lane p cliff routed them to the
    oracle before a sweep could even run. Shapes the dispatcher will
    not serve (ragged, sliver, over-budget) return the budgeted
    default untimed so the cache is never polluted with them."""
    default = resolve_logistic_blocks(n, p)
    if routes_to_oracle(n, p):
        return default

    def make_sweep(interp):
        k0, k1 = jax.random.split(jax.random.PRNGKey(0))
        Xs = jax.random.normal(k0, (m, n, p), dtype)
        ys = jnp.sign(jax.random.normal(k1, (m, n), dtype))
        B = jnp.zeros((m, p), dtype)
        return lambda cand: lambda: logistic_grad_pallas(
            Xs, ys, B, bn=cand[0], bp=cand[1], interpret=interp)

    return _autotune(
        "logistic_grad", {"m": m, "n": n, "p": p}, default,
        logistic_candidates(n, p) if candidates is None else candidates,
        make_sweep, dtype=dtype, backend=backend, interpret=interpret,
        reps=reps, use_disk=use_disk, sweep=sweep)


def autotune_rank_block(m: int, n: int, p: int, *, dtype=jnp.float32,
                        backend: str | None = None,
                        interpret: bool | None = None,
                        candidates: List[Tuple[int, int]] | None = None,
                        reps: int = 2, use_disk: bool = True,
                        sweep: bool = True) -> Tuple[int, int]:
    """Winning (bp, bn) tiling for a (m, n, p) fused rank-n statistics
    update (kernel namespace `rank_update`). As in the logistic sweep,
    shapes the dispatcher routes to the oracle (ragged, sliver tiles)
    return the default untimed so the cache is never polluted with
    unservable keys."""
    from repro.kernels.rank_update.ops import (
        rank_routes_to_oracle, resolve_rank_blocks,
    )
    if rank_routes_to_oracle(n, p):
        return resolve_rank_blocks(n, p, 128)

    def make_sweep(interp):
        k0, k1 = jax.random.split(jax.random.PRNGKey(0))
        Xs = jax.random.normal(k0, (m, n, p), dtype)
        ys = jax.random.normal(k1, (m, n), dtype)
        # tune the unweighted specialization — the always-on ingest case
        return lambda cand: lambda: rank_update_pallas(
            Xs, ys, bp=cand[0], bn=cand[1], interpret=interp)

    return _autotune(
        "rank_update", {"m": m, "n": n, "p": p},
        resolve_rank_blocks(n, p, 128),
        rank_candidates(n, p) if candidates is None else candidates,
        make_sweep, dtype=dtype, backend=backend, interpret=interpret,
        reps=reps, use_disk=use_disk, sweep=sweep)
