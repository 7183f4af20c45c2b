"""Compile every cell's programs at their real shapes for a described
TPU v5e chip, with no chip attached.

The topology (`v5e:2x2`) is described inside module-scoped fixtures,
never while a module is imported. The engine picks its Pallas kernels
and their interpret mode by asking for the backend, which is the CPU
here, so each test steers it to the chip's choices. Covered: the refit
(cold and warm) and the guarded fold of each configuration, every tiling
that the autotune sweep compiles at the wide shapes, the reference fit,
and the refit over four chips with `tenants4`'s mesh (512 tasks on a
data=1 x task=4 mesh).
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from chipbench import check, reference
from chipbench.tests.conftest import CONFIGS

HBM = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - the skip reason carries it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_paths(monkeypatch):
    """The engine's choices on a TPU: kernels, not interpret mode."""
    from repro.kernels import common
    from repro.kernels.ista_step import ops as ista_ops
    from repro.kernels.logistic_grad import ops as logistic_ops
    from repro.kernels.rank_update import ops as rank_ops
    monkeypatch.setattr(common, "kernels_by_default", lambda: True)
    monkeypatch.setattr(ista_ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(rank_ops, "on_tpu", lambda: True)
    monkeypatch.setattr(logistic_ops, "on_tpu", lambda: True)


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _state(m, p, sharding, task_sharding=None):
    from repro.stream.state import StreamState
    t = task_sharding or sharding
    return StreamState(
        Sigmas=_spec((m, p, p), t), cs=_spec((m, p), t),
        counts=_spec((m,), t), beta_local=_spec((m, p), t),
        Ms=_spec((m, p, p), t), beta_u=_spec((m, p), t),
        beta_tilde=_spec((m, p), t), support=_spec((p,), sharding, bool),
        generation=_spec((), sharding, jnp.int32))


def _fits(compiled, what):
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM, f"{what}: {total} bytes on one chip"


@pytest.mark.parametrize("name", sorted(CONFIGS))
@pytest.mark.parametrize("warm", [False, True], ids=["cold", "warm"])
def test_refit_compiles(name, warm, one_chip, chip_paths):
    from repro.stream.refit import refit
    cfg = CONFIGS[name]
    s = cfg["service"]
    lam, mu, Lam = check.penalties(cfg)
    iters = ((s["warm_lasso_iters"], s["warm_debias_iters"]) if warm
             else (s["lasso_iters"], s["debias_iters"]))
    compiled = refit.lower(
        _state(cfg["m"], cfg["p"], one_chip), lam, mu, Lam,
        lasso_iters=iters[0], debias_iters=iters[1], warm=warm,
        tol=s["refit_tol"]).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled, f"{name} refit")


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_guarded_fold_compiles(name, one_chip, chip_paths):
    from repro.stream.guard import _guarded_fold
    cfg = CONFIGS[name]
    m, n, p = cfg["m"], cfg["chunk_n"], cfg["p"]
    compiled = _guarded_fold.lower(
        _state(m, p, one_chip), _spec((m, n, p), one_chip),
        _spec((m, n), one_chip), 1.0).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled, f"{name} fold")


def _wide():
    cfg = CONFIGS["wide-m16-p4096"]
    return cfg["m"], cfg["chunk_n"], cfg["p"]


def _candidates():
    from repro.kernels.autotune import (
        block_candidates, logistic_candidates, rank_candidates,
    )
    m, n, p = _wide()
    out = [("fista", r, c) for r in (1, p) for c in block_candidates(p, r)]
    out += [("rank", n, c) for c in rank_candidates(n, p)]
    out += [("logistic", n, c) for c in logistic_candidates(n, p)]
    return out


@pytest.mark.parametrize("kind,dim,cand", _candidates(),
                         ids=lambda v: "x".join(map(str, v))
                         if isinstance(v, tuple) else str(v))
def test_wide_autotune_candidate_compiles(kind, dim, cand, one_chip):
    from repro.kernels.ista_step.kernel import fista_step_batched_pallas
    from repro.kernels.logistic_grad.kernel import logistic_grad_pallas
    from repro.kernels.rank_update.kernel import rank_update_pallas
    m, n, p = _wide()
    if kind == "fista":
        fn = lambda S, z, c, e: fista_step_batched_pallas(  # noqa: E731
            S, z, z, c, e, 0.1, 0.5, bp=cand[0], br=cand[1], bk=cand[2])
        shapes = [(m, p, p), (m, p, dim), (m, p, dim), (m,)]
    elif kind == "rank":
        fn = lambda X, y: rank_update_pallas(  # noqa: E731
            X, y, bp=cand[0], bn=cand[1])
        shapes = [(m, n, p), (m, n)]
    else:
        fn = lambda X, y, B: logistic_grad_pallas(  # noqa: E731
            X, y, B, bn=cand[0], bp=cand[1])
        shapes = [(m, n, p), (m, n), (m, p)]
    hlo = jax.jit(fn).lower(*[_spec(s, one_chip) for s in shapes]).compile()
    assert "tpu_custom_call" in hlo.as_text()


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reference_fit_compiles(name, one_chip):
    """One block of tasks of the reference fit, as `check.compare` runs
    it once the program's state is freed."""
    cfg = CONFIGS[name]
    ref = cfg["reference"]
    m, p = ref.get("task_block") or cfg["m"], cfg["p"]
    lam, mu, _ = check.penalties(cfg)
    compiled = reference.solve.lower(
        _spec((m, p, p), one_chip), _spec((m, p), one_chip), lam, mu,
        power_iters=ref["power_iters"], lasso_iters=ref["lasso_iters"],
        debias_iters=ref["debias_iters"]).compile()
    _fits(compiled, f"{name} reference fit")


def test_sharded_refit_compiles_on_four_chips(topo, chip_paths):
    """tenants4: 512 tasks at p=1024 on a data=1 x task=4 mesh, so each
    chip solves 128 tasks."""
    from repro.stream.refit import refit
    cfg = dict(CONFIGS["tenants-m384-p1024"], m=512)
    s = cfg["service"]
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "task"),
                axis_types=(AxisType.Auto,) * 2)
    rep, task = NamedSharding(mesh, P()), NamedSharding(mesh, P("task"))
    lam, mu, Lam = check.penalties(cfg)
    compiled = refit.lower(
        _state(cfg["m"], cfg["p"], rep, task), lam, mu, Lam,
        lasso_iters=s["warm_lasso_iters"], debias_iters=s["warm_debias_iters"],
        warm=True, tol=s["refit_tol"], mesh=mesh).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    _fits(compiled, "tenants4 sharded refit")
