"""The whole ingest path's share of the chip's peak FLOP/s: operations
of every fold and refit in the window (`work.py`, from shapes and
iterations run), over the traced window times the peak."""
from chipbench import trace, work


def read(ctx):
    if ctx.trace is None:
        return None
    cfg, w = ctx.cfg, ctx.window
    f, _ = work.fold_chunk(cfg["m"], cfg["chunk_n"], cfg["p"])
    flops = w["chunks"] * f
    lasso, debias = (ctx.hist("stream.refit.lasso_iters"),
                     ctx.hist("stream.refit.debias_iters"))
    if lasso:
        flops += sum(ph[0] for ph in work.refit_phases(
            cfg["m"], cfg["p"], lasso["count"], lasso["sum"], debias["sum"]))
    return 100.0 * flops / (trace.window_s(ctx.trace)
                            * ctx.peaks["flops_per_s"])
