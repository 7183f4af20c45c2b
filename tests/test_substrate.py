"""Substrate-layer tests: optimizer, schedules, checkpointing, data
pipeline, HLO analysis, sharding rules, config registry."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.checkpoint.io import restore_pytree, save_pytree
from repro.configs import ASSIGNED, get_config, smoke
from repro.data.synth_tokens import synthetic_lm_batches
from repro.launch.hlo import analyze_hlo, roofline
from repro.optim.adamw import (
    AdamWState, adamw_init, adamw_update, global_norm, warmup_cosine,
)
from repro.sharding.rules import fit_spec, fit_first

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def _toy_params():
    return {"w": jnp.ones((4, 4), jnp.bfloat16),
            "b": jnp.zeros((4,), jnp.float32)}


def test_adamw_moves_toward_gradient():
    params = _toy_params()
    state = adamw_init(params)
    grads = {"w": jnp.ones((4, 4)), "b": jnp.ones((4,))}
    new_params, state, metrics = adamw_update(grads, state, params, lr=0.1,
                                              weight_decay=0.0)
    assert float(new_params["w"].astype(jnp.float32).mean()) < 1.0
    assert float(metrics["grad_norm"]) > 0


def test_adamw_clipping_bounds_update():
    params = _toy_params()
    state = adamw_init(params)
    huge = {"w": jnp.full((4, 4), 1e6), "b": jnp.full((4,), 1e6)}
    small = {"w": jnp.full((4, 4), 1e-3), "b": jnp.full((4,), 1e-3)}
    p1, _, m1 = adamw_update(huge, state, params, lr=0.1, clip_norm=1.0,
                             weight_decay=0.0)
    p2, _, m2 = adamw_update(small, adamw_init(params), params, lr=0.1,
                             clip_norm=1.0, weight_decay=0.0)
    # after normalization both give the same m/sqrt(v) direction -> same step
    np.testing.assert_allclose(np.asarray(p1["b"]), np.asarray(p2["b"]),
                               atol=1e-5)


def test_adamw_master_weights_do_not_alias_f32_params():
    params = {"r": jnp.ones((3,), jnp.float32)}
    state = adamw_init(params)
    assert state.master["r"] is not params["r"] or \
        state.master["r"].unsafe_buffer_pointer() != params["r"].unsafe_buffer_pointer()


def test_warmup_cosine_shape():
    lrs = [float(warmup_cosine(jnp.asarray(s), peak_lr=1.0, warmup=10,
                               total=100)) for s in range(100)]
    assert lrs[0] > 0                      # step 0 must move params
    assert abs(lrs[9] - 1.0) < 1e-6        # end of warmup == peak
    assert all(a >= b - 1e-6 for a, b in zip(lrs[10:], lrs[11:]))  # decay
    assert lrs[-1] >= 0.1 * 0.9            # floor


def test_global_norm():
    t = {"a": jnp.ones((3,)), "b": -2.0 * jnp.ones((4,))}
    np.testing.assert_allclose(float(global_norm(t)),
                               np.sqrt(3 + 4.0 * 4), rtol=1e-6)


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "nested": {"b": jnp.ones((4,), jnp.bfloat16)},
            "lst": [jnp.zeros((2,), jnp.int32)]}
    path = str(tmp_path / "ckpt")
    save_pytree(path, tree)
    zeros = jax.tree.map(jnp.zeros_like, tree)
    restored = restore_pytree(path, zeros)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32))
        assert a.dtype == b.dtype


def test_checkpoint_shape_mismatch_raises(tmp_path):
    path = str(tmp_path / "ckpt2")
    save_pytree(path, {"a": jnp.ones((2, 2))})
    with pytest.raises(ValueError):
        restore_pytree(path, {"a": jnp.ones((3, 3))})


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_synthetic_lm_batches_learnable_and_sharded():
    it = synthetic_lm_batches(KEY, vocab=64, batch=4, seq=16)
    b1 = next(it)
    b2 = next(it)
    assert b1.tokens.shape == (4, 16)
    assert b1.labels.shape == (4, 16)
    # labels are next-token shifted, last masked
    np.testing.assert_array_equal(np.asarray(b1.labels[:, :-1]),
                                  np.asarray(b1.tokens[:, 1:]))
    assert bool(jnp.all(b1.labels[:, -1] == -1))
    assert not bool(jnp.all(b1.tokens == b2.tokens))   # stream advances


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def test_fit_spec_drops_nondividing_axes():
    mesh = jax.make_mesh((1, 1), ("data", "model"))

    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")
    m = FakeMesh()
    assert fit_spec((24, 128), ("model", None), m) == P(None, None)
    assert fit_spec((32, 128), ("model", None), m) == P("model", None)
    # right alignment adds leading None for stacked params
    assert fit_spec((8, 32, 128), ("model", None), m) == P(None, "model", None)


def test_fit_first_fallback_chain():
    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")
    m = FakeMesh()
    # vocab 49155 not divisible -> falls back to d-over-(data,model)
    spec = fit_first((49155, 2048), (("model", "data"),
                                     (None, ("data", "model"))), m)
    assert spec == P(None, ("data", "model"))


# ---------------------------------------------------------------------------
# HLO analysis
# ---------------------------------------------------------------------------

def test_analyze_hlo_scan_flops_exact():
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y
    s = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    compiled = jax.jit(f).lower(s, s).compile()
    ana = analyze_hlo(compiled.as_text(), default_trip=7)
    assert ana["flops"] == 7 * 2 * 64 ** 3


def test_roofline_bottleneck_selection():
    t = roofline(flops=197e12, bytes_accessed=1.0, coll_bytes=1.0)
    assert t["bottleneck"] == "compute"
    t = roofline(flops=1.0, bytes_accessed=819e9 * 5, coll_bytes=1.0)
    assert t["bottleneck"] == "memory"
    t = roofline(flops=1.0, bytes_accessed=1.0, coll_bytes=50e9 * 5)
    assert t["bottleneck"] == "collective"


# ---------------------------------------------------------------------------
# config registry
# ---------------------------------------------------------------------------

def test_all_assigned_configs_match_spec():
    spec = {
        "minitron-4b": (32, 3072, 24, 8, 9216, 256000),
        "deepseek-moe-16b": (28, 2048, 16, 16, None, 102400),
        "nemotron-4-15b": (32, 6144, 48, 8, 24576, 256000),
        "qwen3-moe-30b-a3b": (48, 2048, 32, 4, None, 151936),
        "seamless-m4t-medium": (12, 1024, 16, 16, 4096, 256206),
        "internvl2-2b": (24, 2048, 16, 8, 8192, 92553),
        "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
        "deepseek-67b": (95, 8192, 64, 8, 22016, 102400),
        "granite-3-2b": (40, 2048, 32, 8, 8192, 49155),
        "mamba2-1.3b": (48, 2048, 64, 0, 0, 50280),
    }
    for arch, (L, d, nh, kv, ff, v) in spec.items():
        cfg = get_config(arch)
        assert cfg.n_layers == L, arch
        assert cfg.d_model == d, arch
        assert cfg.n_heads == nh, arch
        assert cfg.n_kv_heads == kv, arch
        if ff is not None:
            assert cfg.d_ff == ff, arch
        assert cfg.vocab == v, arch
    # MoE details
    q = get_config("qwen3-moe-30b-a3b").moe
    assert (q.n_experts, q.top_k, q.n_shared) == (128, 8, 0)
    ds = get_config("deepseek-moe-16b").moe
    assert (ds.n_experts, ds.top_k, ds.n_shared, ds.d_expert) == (64, 6, 2, 1408)
    mm = get_config("mamba2-1.3b").ssd
    assert mm.state_dim == 128


def test_smoke_configs_are_reduced():
    for arch in ASSIGNED:
        cfg = smoke(get_config(arch))
        assert cfg.n_layers <= 3
        assert cfg.d_model <= 512
        if cfg.moe:
            assert cfg.moe.n_experts <= 4


# ---------------------------------------------------------------------------
# subprocess probes
# ---------------------------------------------------------------------------

def test_run_probe_child_stays_on_cpu(monkeypatch):
    """Probe children are CPU host-device probes by definition: even
    when the parent's environment names an accelerator platform, the
    child gets JAX_PLATFORMS=cpu and never reaches for a chip the
    parent may hold."""
    from repro.substrate import host_device_env, run_probe
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    assert host_device_env(2)["JAX_PLATFORMS"] == "cpu"
    res = run_probe("import jax; print(jax.devices()[0].platform, "
                    "jax.device_count())", n_devices=2, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    assert res.stdout.split() == ["cpu", "2"]
