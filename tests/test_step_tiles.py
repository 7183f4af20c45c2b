"""The FISTA step's tile policy: which (bp, br, bk) tilings the autotuner
sweeps, the VMEM each asks the compiler for, and the cache key that
keeps a winner of one candidate list from being served for another.

The many-right-hand-side step (the M solve, r = p) reads Sigma r / br
times and z p / bp times per step, so its candidates are wide output
tiles bought with a per-call VMEM limit; the lasso's one right-hand side
reads Sigma once whatever its tile, and its candidates stay as they
were. Whether a tiling compiles for the chip is `test_tpu_compile.py`'s
part; here everything is CPU-only.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.kernels import autotune
from repro.kernels.autotune import MAX_CANDIDATES, block_candidates
from repro.kernels.ista_step.kernel import (
    DEFAULT_SCOPED_VMEM, MIB, fista_step_batched_pallas, step_vmem_bytes,
    step_vmem_limit,
)
from repro.kernels.ista_step.ops import STEP_VMEM_BUDGET, fista_step_batched

PARENT_TILE = (256, 512, 256)       # the r = p winner under the old 8 MB cap


def test_r_eq_p_candidates_at_p1024():
    cands = block_candidates(1024, 1024)
    assert len(cands) == 7
    assert len(set(cands)) == 7
    assert any(bp * br > PARENT_TILE[0] * PARENT_TILE[1]
               for bp, br, _ in cands)
    # the contraction tile streams: it is no longer tied to bp
    assert {bk for _, _, bk in cands} <= {128, 256, 512}
    assert any(bk != bp for bp, _, bk in cands)
    assert (128, 128, 128) not in cands


@pytest.mark.parametrize("p,r", [(1024, 1024), (4096, 4096), (2048, 2048),
                                 (512, 512), (384, 384), (200, 200),
                                 (1024, 128), (4096, 1024)])
def test_at_most_seven_legal_candidates(p, r):
    cands = block_candidates(p, r)
    assert 1 <= len(cands) <= MAX_CANDIDATES
    for bp, br, bk in cands:
        assert p % bp == 0 and (bp % 8 == 0 or bp == p)
        assert r % br == 0 and (br % 128 == 0 or br == r)
        assert p % bk == 0 and (bk % 128 == 0 or bk == p)


@pytest.mark.parametrize("p", [1024, 4096])
def test_r_eq_p_candidates_fit_the_cap_and_their_limit(p):
    for cand in block_candidates(p, p):
        need = step_vmem_bytes(*cand)
        assert need <= STEP_VMEM_BUDGET, cand
        limit = step_vmem_limit(*cand)
        assert (limit or DEFAULT_SCOPED_VMEM) >= need, cand
        assert limit is None or limit % MIB == 0
        # a v5e core holds 128 MiB of VMEM
        assert (limit or 0) <= 128 * MIB


def test_byte_model_matches_what_mosaic_allocates():
    """The scoped allocations a compile for a described v5e reported
    (MiB), from the small tiles to past the default 16 MiB limit."""
    reported = {(512, 512, 512): 18.0, (512, 512, 256): 16.0,
                (256, 512, 256): 8.5, (1024, 1024, 256): 60.0,
                (512, 2048, 128): 58.5, (1024, 2048, 256): 118.0}
    for cand, mib in reported.items():
        assert step_vmem_bytes(*cand) == mib * MIB, cand


def test_limit_only_where_the_default_does_not_hold():
    assert step_vmem_limit(*PARENT_TILE) is None
    assert step_vmem_limit(512, 1, 512) is None
    assert step_vmem_limit(512, 512, 512) == 22 * MIB
    assert step_vmem_limit(1024, 1024, 256) == 64 * MIB


@pytest.mark.parametrize("p", [1024, 4096])
def test_one_rhs_candidates_unchanged(p):
    assert block_candidates(p, 1) == [(128, 1, 128), (256, 1, 256),
                                      (512, 1, 512)]


def test_cache_key_names_the_candidate_list():
    dims = {"m": 384, "p": 1024, "r": 1024}
    a = autotune.cache_key("fista_step", "tpu", dims, jnp.float32,
                           [PARENT_TILE])
    b = autotune.cache_key("fista_step", "tpu", dims, jnp.float32,
                           block_candidates(1024, 1024))
    bare = autotune.cache_key("fista_step", "tpu", dims, jnp.float32)
    assert len({a, b, bare}) == 3
    assert a.startswith(bare + "@") and b.startswith(bare + "@")
    assert a == autotune.cache_key("fista_step", "tpu", dims, jnp.float32,
                                   [PARENT_TILE])


def test_winner_of_another_list_is_not_served(tmp_path, monkeypatch):
    """A disk cache that outlives a change of candidates (the parent's
    winner under its own list, and under the bare key of older files)
    is read, not hit: the new list is swept and its winner stored
    beside the old entries."""
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    autotune.clear_memory_cache()
    m, p, r = 2, 256, 256
    old_list = [(128, 256, 128)]
    new_list = [(256, 256, 128), (256, 256, 256)]
    dims = {"m": m, "p": p, "r": r}
    backend = jax.default_backend()
    stale = {autotune.cache_key("fista_step", backend, dims, jnp.float32,
                                old_list): list(old_list[0]),
             autotune.cache_key("fista_step", backend, dims,
                                jnp.float32): list(old_list[0])}
    autotune.cache_path().parent.mkdir(parents=True, exist_ok=True)
    autotune.cache_path().write_text(json.dumps(stale))
    timed = []
    monkeypatch.setattr(autotune, "_time_candidate",
                        lambda fn, reps: timed.append(1) or len(timed))
    blk = autotune.autotune_block(m, p, r, candidates=new_list, reps=1)
    assert blk in new_list and len(timed) == len(new_list)
    disk = json.loads(autotune.cache_path().read_text())
    assert len(disk) == 3 and all(disk[k] == v for k, v in stale.items())
    autotune.clear_memory_cache()
    assert autotune.autotune_block(m, p, r, candidates=old_list,
                                   reps=1) == old_list[0]
    assert len(timed) == len(new_list)          # served from disk


def _step_inputs(m, p, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    A = jax.random.normal(k[0], (m, p, p), jnp.float32) / np.sqrt(p)
    Sigmas = jnp.einsum("tij,tkj->tik", A, A) + 0.1 * jnp.eye(p)
    zs = jax.random.normal(k[1], (m, p, p), jnp.float32)
    xs = jax.random.normal(k[2], (m, p, p), jnp.float32)
    cs = jnp.broadcast_to(jnp.eye(p, dtype=jnp.float32), (m, p, p))
    etas = jnp.full((m,), 0.5, jnp.float32)
    return Sigmas, zs, xs, cs, etas


def test_decoupled_wide_tiles_agree_with_parent_style_tiles():
    """One step at (2, 512, 512): the parent's kind of tiling (bk tied
    to bp) and a wide output tile streaming a narrower bk differ only
    in the order of the f32 accumulation."""
    Sigmas, zs, xs, cs, etas = _step_inputs(2, 512)
    outs = [fista_step_batched_pallas(Sigmas, zs, xs, cs, etas, 0.05, 0.3,
                                      bp=bp, br=br, bk=bk, interpret=True)
            for bp, br, bk in ((256, 512, 256), (512, 512, 128))]
    for a, b in zip(*outs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
        assert float(jnp.max(jnp.abs(a))) > 0.1


def test_route_label_names_the_vmem_limit():
    """`dispatch.route` carries the tiling and the limit the call asks
    for, so a snapshot taken after the refit compiles says which the M
    solve compiled with."""
    Sigmas, zs, xs, cs, etas = _step_inputs(1, 512, seed=1)
    obs.reset()
    for block in ((512, 512, 128), PARENT_TILE):
        fista_step_batched(Sigmas, zs, xs, jnp.zeros_like(zs), cs, etas,
                           0.05, 0.3, block=block, interpret=True)
    label = lambda blocks, mib: obs.counter_total(  # noqa: E731
        "dispatch.route", kernel="fista_step_batched", outcome="kernel",
        blocks=blocks, vmem_mib=mib)
    assert label("512x512x128", str(step_vmem_limit(512, 512, 128) // MIB)) \
        == 1
    assert label("256x512x256", "default") == 1
    obs.reset()
