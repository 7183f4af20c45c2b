"""The paper's technique as a framework feature: communication-efficient
multi-task sparse probes on frozen backbone features (DESIGN.md §5).

Four "machines" each own a task (their own labelled data); the backbone
is shared and frozen. DSML recovers the common sparse support over
feature dimensions with ONE round of communication.

    PYTHONPATH=src python examples/multitask_probes.py
"""
import jax
import jax.numpy as jnp

from repro.configs import get_config, smoke
from repro.models import init_params
from repro.multitask import (
    probe_predict, sparse_probe_fit, synthetic_probe_tasks,
)


def main():
    cfg = smoke(get_config("granite-3-2b")).replace(
        compute_dtype="float32", param_dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    print(f"backbone: {cfg.name} (reduced) d_model={cfg.d_model}")

    data, support = synthetic_probe_tasks(jax.random.PRNGKey(1), params,
                                          cfg, m=4, n=96, s_active=6)
    print(f"tasks=4, samples/task=96, active feature dims={int(support.sum())}")

    res = sparse_probe_fit(data)
    tp = int(jnp.sum(res.support & support))
    fp = int(jnp.sum(res.support & ~support))
    print(f"recovered support: {tp}/{int(support.sum())} true dims, "
          f"{fp} false positives")

    pred = probe_predict(res, data.features)
    r2 = 1 - float(jnp.var(pred - data.targets) / jnp.var(data.targets))
    print(f"fit R^2 = {r2:.3f}")
    d = cfg.d_model
    print(f"communication: one round of {d} floats per task "
          f"(vs shipping {data.features.shape[1]}x{d} features per task)")


if __name__ == "__main__":
    from repro.substrate import enable_compile_cache
    enable_compile_cache()
    main()
