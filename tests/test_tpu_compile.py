"""Compile the main path's Pallas kernels for a described TPU v5e chip.

No chip is attached: `jax.experimental.topologies` describes a v5e:2x2
host and the TPU compiler builds each kernel for one of its chips, so a
tiling the chip's compiler refuses (a block that breaks the (8, 128)
rule, a kernel over its VMEM) fails here instead of on the chip. Shapes
are the chip smoke's (m=128 tasks, p=1024 features, n=512-row chunks),
the paper's p=200 through the dispatcher's resolved blocks, and every
candidate `autotune.warmup_cache` would sweep at the smoke's shapes.

The topology is described only inside a module-scoped fixture: loading
the TPU library is a per-process lock, so it must happen in the one
test worker that runs this file, never while modules are imported.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.autotune import (
    block_candidates, logistic_candidates, rank_candidates,
)
from repro.kernels.ista_step.kernel import fista_step_batched_pallas
from repro.kernels.ista_step.ops import fista_step_batched, resolve_blocks
from repro.kernels.logistic_grad.kernel import logistic_grad_pallas
from repro.kernels.logistic_grad.ops import logistic_grad
from repro.kernels.rank_update.kernel import rank_update_pallas
from repro.kernels.rank_update.ops import rank_update

M, P, N = 128, 1024, 512          # chip_smoke.py's deployment
PAPER_M, PAPER_P = 10, 200        # the paper's Section-6 regime


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - skip reason carries it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("r", [1, P], ids=["r1", "rp"])
def test_fista_step_compiles_at_smoke_shape(one_chip, r):
    _compile(lambda S, z, c, e: fista_step_batched(
        S, z, z, c, e, 0.1, 0.5, interpret=False),
        [(M, P, P), (M, P, r), (M, P, r), (M,)], one_chip)


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["unweighted", "weighted"])
def test_rank_update_compiles_at_smoke_shape(one_chip, weighted):
    if weighted:
        fn = lambda X, y, w: rank_update(X, y, w, use_kernel=True,
                                         interpret=False)
        shapes = [(M, N, P), (M, N), (M, N)]
    else:
        fn = lambda X, y: rank_update(X, y, use_kernel=True,
                                      interpret=False)
        shapes = [(M, N, P), (M, N)]
    _compile(fn, shapes, one_chip)


def test_logistic_grad_compiles_at_smoke_shape(one_chip):
    _compile(lambda X, y, B: logistic_grad(X, y, B, interpret=False),
             [(M, N, P), (M, N), (M, P)], one_chip)


@pytest.mark.parametrize("r", [1, PAPER_P], ids=["r1", "rp"])
def test_paper_p200_compiles_through_dispatch(one_chip, r):
    """p = 200 has no 128-multiple divisor: the resolver must hand the
    kernel whole-axis lane tiles, never the (40, 40) block the chip's
    compiler refuses."""
    assert resolve_blocks(PAPER_P, r, 128)[2] == PAPER_P
    _compile(lambda S, z, c, e: fista_step_batched(
        S, z, z, c, e, 0.1, 0.5, interpret=False),
        [(PAPER_M, PAPER_P, PAPER_P), (PAPER_M, PAPER_P, r),
         (PAPER_M, PAPER_P, r), (PAPER_M,)], one_chip)


@pytest.mark.parametrize(
    "r,cand", [(r, c) for r in (1, P) for c in block_candidates(P, r)],
    ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_fista_autotune_candidate_compiles(one_chip, r, cand):
    bp, br, bk = cand
    _compile(lambda S, z, c, e: fista_step_batched_pallas(
        S, z, z, c, e, 0.1, 0.5, bp=bp, br=br, bk=bk),
        [(M, P, P), (M, P, r), (M, P, r), (M,)], one_chip)


@pytest.mark.parametrize("cand", logistic_candidates(N, P),
                         ids=lambda c: "x".join(map(str, c)))
def test_logistic_autotune_candidate_compiles(one_chip, cand):
    bn, bp = cand
    _compile(lambda X, y, B: logistic_grad_pallas(X, y, B, bn=bn, bp=bp),
             [(M, N, P), (M, N), (M, P)], one_chip)


@pytest.mark.parametrize("cand", rank_candidates(N, P),
                         ids=lambda c: "x".join(map(str, c)))
def test_rank_autotune_candidate_compiles(one_chip, cand):
    bp, bn = cand
    _compile(lambda X, y: rank_update_pallas(X, y, bp=bp, bn=bn),
             [(M, N, P), (M, N)], one_chip)
