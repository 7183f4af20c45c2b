"""Device time of the refit's lasso (`refit.lasso` ops, `phases.py`) per
refit in the window."""
from chipbench import phases


def read(ctx):
    return phases.per_refit_ms(ctx, "refit.lasso")
