"""Compile the main path's Pallas kernels for a described TPU v5e chip.

No chip is attached: `jax.experimental.topologies` describes a v5e:2x2
host and the TPU compiler builds each kernel for one of its chips, so a
tiling the chip's compiler refuses (a block that breaks the (8, 128)
rule, a kernel over its VMEM) fails here instead of on the chip. Shapes
are the chip smoke's (m=128 tasks, p=1024 features, n=512-row chunks),
the paper's p=200 through the dispatcher's resolved blocks, and every
candidate `autotune.warmup_cache` would sweep at the smoke's shapes, and
the widest M-solve tiling at each deployment's per-chip shape.
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
from repro.kernels.ista_step.kernel import (
    fista_step_batched_pallas, step_vmem_bytes, step_vmem_limit,
)
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


# each deployment's per-chip M solve: the widest r = p tiling the
# autotuner may pick, with the VMEM limit its call asks for
MSOLVE_SHAPES = [(384, 1024), (16, 4096), (256, 1024)]


@pytest.mark.parametrize("m,p", MSOLVE_SHAPES,
                         ids=[f"m{m}-p{p}" for m, p in MSOLVE_SHAPES])
def test_widest_msolve_tile_compiles_with_its_vmem_limit(one_chip, m, p):
    cand = max(block_candidates(p, p), key=lambda c: step_vmem_bytes(*c))
    assert step_vmem_limit(*cand) is not None      # past the default
    _compile(lambda S, z, x, w, c, e: fista_step_batched(
        S, z, x, w, c, e, 0.1, 0.5, block=cand, interpret=False),
        [(m, p, p)] * 5 + [(m,)], one_chip)


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


# the four-chip many-tenant deployment: 1,024 tasks task-sharded over a
# data=1 x task=4 mesh, 256 tasks per chip, 512-row chunks
T4_M, T4_N, T4_P = 1024, 512, 1024
CHIP_BYTES = 16e9
COLLECTIVE = re.compile(r"= (\S+) (?:all-gather|all-reduce|all-to-all|"
                        r"collective-permute|reduce-scatter)[-a-z]*\(")


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices).reshape(1, 4), ("data", "task"),
                axis_types=(AxisType.Auto,) * 2)


def _t4_state(mesh):
    from repro.stream.state import StreamState, state_shardings
    at = state_shardings(mesh)
    m, p = T4_M, T4_P
    return StreamState(*[
        jax.ShapeDtypeStruct(shape, dtype, sharding=getattr(at, f))
        for f, shape, dtype in zip(StreamState._fields, [
            (m, p, p), (m, p), (m,), (m, p), (m, p, p), (m, p), (m, p),
            (p,), ()], [jnp.float32] * 7 + [bool, jnp.int32])])


def _t4_chunk(mesh):
    from repro.substrate import chunk_specs
    sx, sy = chunk_specs()
    return (jax.ShapeDtypeStruct((T4_M, T4_N, T4_P), jnp.float32,
                                 sharding=NamedSharding(mesh, sx)),
            jax.ShapeDtypeStruct((T4_M, T4_N), jnp.float32,
                                 sharding=NamedSharding(mesh, sy)))


def _per_chip(compiled, what) -> str:
    """The program's bytes on each chip under 16 GB; no collective moves
    more than p values (never an (m, p, p) or (m, p) stack). Returns
    the HLO text."""
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < CHIP_BYTES, f"{what}: {total} bytes on one chip"
    hlo = compiled.as_text()
    for typ in COLLECTIVE.findall(hlo):
        dims = [int(d) for d in re.findall(r"\d+", typ.split("{")[0])[1:]]
        assert int(np.prod(dims)) <= T4_P, f"{what}: collective {typ}"
    return hlo


@pytest.mark.parametrize("warm", [False, True], ids=["cold", "warm"])
def test_tenants4_sharded_refit_fits_each_chip(mesh4, chip_paths, warm):
    """The 1,024-task refit on four chips: each chip holds its 256
    tasks' stacks, its M solve runs the kernel in place on a (256, 1024,
    1024) shard, and only the threshold's (p,) reduction crosses chips."""
    from repro.stream.refit import refit
    iters = (100, 150) if warm else (400, 600)
    compiled = refit.lower(
        _t4_state(mesh4), 0.33, 0.08, 6.4, lasso_iters=iters[0],
        debias_iters=iters[1], warm=warm, tol=1e-5, mesh=mesh4).compile()
    hlo = _per_chip(compiled, "tenants4 refit")
    _assert_msolve_in_place(hlo, T4_M // 4, T4_P)


def test_tenants4_mesh_ingest_fits_each_chip(mesh4):
    """The state born on the mesh (each chip builds only its 256 tasks'
    stacks), the mesh fold, the guard's probe of the fed chunk and the
    refit's health check over the sharded candidate: nothing gathered,
    each within one chip."""
    from functools import partial

    from repro.stream.accumulate import ingest_sharded
    from repro.stream.guard import mesh_health
    from repro.stream.health import _model_health
    from repro.stream.state import init_stream_state, state_shardings
    born = jax.jit(partial(init_stream_state, T4_M, T4_P),
                   out_shardings=state_shardings(mesh4)).lower().compile()
    _per_chip(born, "tenants4 state")
    # two (256, 1024, 1024) float32 stacks per chip, and small change
    assert born.memory_analysis().output_size_in_bytes < 2.2e9
    state = _t4_state(mesh4)
    X, y = _t4_chunk(mesh4)
    _per_chip(ingest_sharded.lower(state, X, y, mesh4, 1.0).compile(),
              "tenants4 fold")
    _per_chip(mesh_health(mesh4).lower(X, y).compile(), "tenants4 probe")
    _per_chip(_model_health.lower(
        state.Sigmas, state.cs, state.beta_local, state.Ms, state.beta_u,
        state.beta_tilde, 0.33).compile(), "tenants4 health")
