"""The refit's phases on each chip of a mesh cell.

`phases.py` reads the phase of each op of `jit_refit` from the one-chip
refit it compiles again from a cell's shapes. A cell with a `mesh` runs
the task-sharded refit, a different program, so this module compiles
that one: the warm refit on the same data x task mesh of the devices
the run used, from the configuration's shapes with the state laid out
as the service keeps it (per-task fields over `task`, the support and
the generation replicated). JAX hands back the executable the run
compiled. Every chip runs the same program, so one map from op name to
phase serves all of them.
"""
from __future__ import annotations

from chipbench import phases, trace


def program_op_phases(cfg) -> dict:
    """`phases.hlo_op_phases` of the warm sharded refit of a mesh cell."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from chipbench.check import penalties
    from repro.stream.refit import refit
    from repro.stream.state import StreamState
    from repro.substrate import data_task_mesh
    shape, s = cfg["mesh"], cfg["service"]
    mesh = data_task_mesh(n_task=shape["task"], n_data=shape["data"])
    task, rep = NamedSharding(mesh, P("task")), NamedSharding(mesh, P())
    m, p = cfg["m"], cfg["p"]

    def spec(dims, sharding, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)
    state = StreamState(
        Sigmas=spec((m, p, p), task), cs=spec((m, p), task),
        counts=spec((m,), task), beta_local=spec((m, p), task),
        Ms=spec((m, p, p), task), beta_u=spec((m, p), task),
        beta_tilde=spec((m, p), task), support=spec((p,), rep, bool),
        generation=spec((), rep, jnp.int32))
    lam, mu, Lam = penalties(cfg)
    compiled = refit.lower(
        state, lam, mu, Lam, lasso_iters=s["warm_lasso_iters"],
        debias_iters=s["warm_debias_iters"], warm=True, tol=s["refit_tol"],
        mesh=mesh).compile()
    return phases.hlo_op_phases(compiled.as_text())


def attach(ctx) -> bool:
    """`phases.attach` for a mesh cell: every chip of the trace gets its
    `phases`. False where there is no trace, the cell has no mesh, the
    run was not on the chip, or an op of the trace's refit is not in the
    program compiled here."""
    tr = ctx.trace
    if tr is None or ctx.cfg.get("mesh") is None:
        return False
    if all("phases" in dev for dev in tr["devices"]):
        return True
    import jax
    if (not tr["devices"][0]["name"].startswith(trace.DEVICE_PREFIX)
            or jax.devices()[0].platform != "tpu"):
        return False
    op_phase = program_op_phases(ctx.cfg)
    runs = [phases.executions(dev) for dev in tr["devices"]]
    if any(phases._short(o[0]) not in op_phase
           for dev_runs in runs for ops in dev_runs for o in ops):
        return False
    for dev, dev_runs in zip(tr["devices"], runs):
        dev["phases"] = [e for ops in dev_runs
                         for e in phases.assign(ops, op_phase)]
    return True


def busy_by_device(ctx, phase: str):
    """Seconds in which an op of `phase` ran in the window, one entry
    per chip; None when the trace holds no such op."""
    if not attach(ctx):
        return None
    out = []
    for dev in ctx.trace["devices"]:
        mine = [e for e in dev["phases"] if e[0] == phase]
        out.append(sum(b - a for a, b in trace.union(
            trace.clip(mine, ctx.trace["window"]))))
    return out if any(out) else None


def program_times(tr, program: str = phases.PROGRAM) -> list:
    """Device seconds of each execution of `program` in the window, as
    one list per chip, in the order they ran."""
    return [[b - a for name, a, b in sorted(
                trace.clip(dev["modules"], tr["window"]), key=lambda e: e[1])
             if trace.module_base(name) == program]
            for dev in tr["devices"]]
