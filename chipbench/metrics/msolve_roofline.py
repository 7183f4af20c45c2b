"""The M solve's share of its roofline: the least time of the debias
iterations the window's refits ran (`work.debias_step`, from shapes and
iterations), over the device time of the `refit.msolve` ops
(`phases.py`)."""
from chipbench import phases, work


def read(ctx):
    debias = ctx.hist("stream.refit.debias_iters")
    busy = phases.busy_s(ctx, "refit.msolve")
    if busy is None or not debias:
        return None
    f, b = work.debias_step(ctx.cfg["m"], ctx.cfg["p"])
    k = debias["sum"]
    return 100.0 * work.least_time([(k * f, k * b)], ctx.peaks) / busy
