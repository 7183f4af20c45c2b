"""The refit program's share of its roofline: the least time that the
window's refits need on this chip (power iteration, lasso, M solve and
debias, counted from shapes and the iterations they ran, `work.py`),
over the refit program's device time in the trace."""
from chipbench import trace, work

MODULES = {"jit_refit"}


def read(ctx):
    lasso, debias = (ctx.hist("stream.refit.lasso_iters"),
                     ctx.hist("stream.refit.debias_iters"))
    if ctx.trace is None or not lasso:
        return None
    m, p = ctx.cfg["m"], ctx.cfg["p"]
    least = work.least_time(work.refit_phases(
        m, p, lasso["count"], lasso["sum"], debias["sum"]), ctx.peaks)
    return 100.0 * least / trace.module_time(ctx.trace, MODULES)
