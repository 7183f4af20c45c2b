"""One chip's M solve against its roofline on a mesh: the least time of
one task shard's M solve (`work.debias_step` at m / task tasks, times
the largest shard's iterations in each refit of the window), over the
`refit.msolve` device time of the slowest chip (`mesh_phases.py`)."""
from chipbench import mesh_phases, work


def read(ctx):
    if not ctx.hist("stream.refit.shard_debias_iters"):
        return None
    largest = ctx.hist("stream.refit.debias_iters")
    busy = mesh_phases.busy_by_device(ctx, "refit.msolve")
    if busy is None or not largest:
        return None
    cfg = ctx.cfg
    f, b = work.debias_step(cfg["m"] // cfg["mesh"]["task"], cfg["p"])
    k = largest["sum"]
    return 100.0 * work.least_time([(k * f, k * b)], ctx.peaks) / max(busy)
