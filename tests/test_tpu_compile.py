"""Compile the main path's Pallas kernels for a described TPU v5e chip.

No chip is attached: `jax.experimental.topologies` describes a v5e:2x2
host and the TPU compiler builds each kernel for one of its chips, so a
tiling the chip's compiler refuses (a block that breaks the (8, 128)
rule, a kernel over its VMEM) fails here instead of on the chip. Shapes
are the chip smoke's (m=128 tasks, p=1024 features, n=512-row chunks),
the paper's p=200 through the dispatcher's resolved blocks, and every
candidate `autotune.warmup_cache` would sweep at the smoke's shapes.
The warm refit is compiled at the benchmark's two deployments, on one
chip and sharded over four, and its M solve is checked to run in place.

The topology is described only inside a module-scoped fixture: loading
the TPU library is a per-process lock, so it must happen in the one
test worker that runs this file, never while modules are imported.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec

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
REFIT_SHAPES = [(384, 1024), (16, 4096)]   # the benchmark's deployments


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
        S, z, z, jnp.zeros_like(z), c, e, 0.1, 0.5, interpret=False),
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
        S, z, z, jnp.zeros_like(z), c, e, 0.1, 0.5, interpret=False),
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


@pytest.fixture
def chip_paths(monkeypatch):
    """The engine's choices on a TPU: kernels, not interpret mode."""
    from repro.kernels import common
    from repro.kernels.ista_step import ops as ista_ops
    monkeypatch.setattr(common, "kernels_by_default", lambda: True)
    monkeypatch.setattr(ista_ops, "_on_tpu", lambda: True)


def _warm_refit_hlo(m, p, sharding, task_sharding=None, mesh=None):
    """The service's warm refit (tol set, ceilings 100/150), compiled."""
    from repro.stream.refit import refit
    from repro.stream.state import StreamState
    spec = lambda shape, sh, dtype=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=sh)
    t = task_sharding or sharding
    state = StreamState(
        Sigmas=spec((m, p, p), t), cs=spec((m, p), t), counts=spec((m,), t),
        beta_local=spec((m, p), t), Ms=spec((m, p, p), t),
        beta_u=spec((m, p), t), beta_tilde=spec((m, p), t),
        support=spec((p,), sharding, bool),
        generation=spec((), sharding, jnp.int32))
    extra = {} if mesh is None else {"mesh": mesh}
    return refit.lower(state, 0.1, 0.05, 1.0, lasso_iters=100,
                       debias_iters=150, warm=True, tol=1e-5,
                       **extra).compile().as_text()


def _msolve_computations(hlo: str, m: int, p: int) -> list:
    """(M-solve steps, copies of an f32[m,p,p] stack) of every HLO
    computation that holds an M-solve step: a TPU custom call whose
    outputs are (m, p, p) stacks."""
    stack = f"f32[{m},{p},{p}]"
    out = []
    for comp in re.split(r"\n(?=\S.*\{\s*$)", hlo, flags=re.M):
        steps = copies = 0
        for line in comp.split("\n"):
            typ = re.search(r"= (\(?\S+)", line)
            if typ is None:
                continue
            if "tpu_custom_call" in line and typ[1].startswith(f"({stack}"):
                steps += 1
            elif typ[1].startswith(stack) and re.search(r" copy\(", line):
                copies += 1
        if steps:
            out.append((steps, copies))
    return out


def _assert_msolve_in_place(hlo: str, m: int, p: int) -> None:
    """The M solve's pair loop holds two steps and no copy of a stack;
    the unpaired step of an odd chunk may copy z' back, once."""
    comps = _msolve_computations(hlo, m, p)
    assert (2, 0) in comps, comps
    assert sum(c for _, c in comps) <= 1, comps


@pytest.mark.parametrize("m,p", REFIT_SHAPES,
                         ids=[f"m{m}-p{p}" for m, p in REFIT_SHAPES])
def test_warm_refit_msolve_runs_in_place(one_chip, chip_paths, m, p):
    _assert_msolve_in_place(_warm_refit_hlo(m, p, one_chip), m, p)


def test_sharded_warm_refit_compiles_in_place(topo, chip_paths):
    """The refit over a data=1 x task=4 mesh: each chip solves its 128
    tasks' stacks, in place."""
    m, p = 512, 1024
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "task"),
                axis_types=(AxisType.Auto,) * 2)
    hlo = _warm_refit_hlo(m, p, NamedSharding(mesh, PartitionSpec()),
                          NamedSharding(mesh, PartitionSpec("task")), mesh)
    assert "tpu_custom_call" in hlo
    _assert_msolve_in_place(hlo, m // 4, p)
